"""The fixed yardstick: traffic, weights, references, FLOP counts, trace
reduction and the end-to-end arithmetic."""
