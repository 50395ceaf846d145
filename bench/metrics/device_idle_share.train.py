"""Share of the traced slice in which no operation ran on the device,
averaged over the devices the cell uses."""


def read(trace, record):
    return 100.0 * trace.idle_share()
