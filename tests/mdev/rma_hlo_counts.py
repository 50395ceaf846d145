import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
# hermetic accumulate routing: ignore any local calibration artifact and pin
# the crossover to the hardware-envelope default
os.environ["RMA_ACC_BENCH_JSON"] = "/nonexistent"
os.environ.pop("RMA_ACC_CROSSOVER", None)
import os, sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.rma import Window, WindowConfig, rma_all_reduce, put_signal
from repro import compat

N = 8
mesh = compat.make_mesh((N,), ("x",))

def count_cp(f, optimized=True):
    g = jax.jit(compat.shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P("x")))
    low = g.lower(jnp.zeros((N*4,), jnp.float32))
    txt = low.compile().as_text() if optimized else low.as_text(dialect="hlo")
    return txt.count("collective-permute(")  , txt.count("collective-permute-start(")

# put_signal listing1 (no order) vs listing2 (order)
def mk(order):
    def f(x):
        win = Window.allocate(x, "x", N, WindowConfig(order=order))
        win = put_signal(win, jnp.full((2,), 3.0), [(0,1)], data_offset=0, flag_offset=3)
        win = win.flush()
        return win.buffer
    return f
l1 = count_cp(mk(False))[0]; l2 = count_cp(mk(True))[0]
print("listing1 (flush between):", l1)
print("listing2 (ordered):      ", l2)
assert l2 < l1, "P2 ordering must remove the intermediate flush phases"

# process vs thread flush with 4 streams
def mkflush(scope):
    def f(x):
        win = Window.allocate(x, "x", N, WindowConfig(scope=scope, max_streams=4))
        perm = [(i,(i+1)%N) for i in range(N)]
        for s in range(4):
            win = win.put(jnp.full((2,), 1.0+s), perm, offset=0, stream=s)
        win = win.flush(stream=0)
        return win.buffer
    return f
pf = count_cp(mkflush("process"))[0]; tf = count_cp(mkflush("thread"))[0]
print("process-scope flush, 4 streams:", pf)
print("thread-scope flush, 4 streams: ", tf)
assert tf < pf, "P1 thread-scope flush must avoid the endpoint-list walk"

# ring allreduce order vs not
counts = {}
for order in (True, False):
    def f(x, order=order):
        return rma_all_reduce(x, "x", N, order=order)
    counts[order] = count_cp(f)[0]
    print(f"rma_all_reduce order={order}:", counts[order])
assert counts[True] == 2 * (N - 1), "ordered ring = 2(n-1) data phases"
assert counts[False] > counts[True], "no-P2 baseline pays per-hop flush phases"

# --- accumulate engine: op x dtype x size matrix -> lowered path phase counts
# one accumulate + flush; expected collective-permutes per routed path:
#   intrinsic: 1 (data)            + 2 (flush ack RTT) = 3
#   tiled:     1 (data; VPU kernel adds no phases)     + 2 = 3
#   software:  1 (data) + 1 (completion ack)           + 2 = 4
def count_cp_n(f, n_elems):
    g = jax.jit(compat.shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P("x")))
    txt = g.lower(jnp.zeros((N * n_elems,), jnp.float32)).compile().as_text()
    return txt.count("collective-permute(")

MATRIX = [
    # (op, count, dtype, config kwargs, expected path, expected phases)
    ("sum",     4, jnp.float32, dict(same_op="sum"),                     "intrinsic", 3),
    ("sum",    64, jnp.float32, dict(same_op="sum"),                     "tiled",     3),
    ("sum",     4, jnp.float32, dict(),                                  "software",  4),
    ("sum",    64, jnp.float32, dict(),                                  "software",  4),
    ("min",     4, jnp.int32,   dict(same_op="min",
                                     accumulate_ops=("min",)),           "intrinsic", 3),
    ("min",    64, jnp.int32,   dict(same_op="min",
                                     accumulate_ops=("min",)),           "tiled",     3),
    ("prod",    4, jnp.float32, dict(same_op="prod",
                                     accumulate_ops=("prod",)),          "tiled",     3),  # NICs don't multiply
    ("sum",     4, jnp.bfloat16, dict(same_op="sum"),                    "tiled",     3),  # no short-float atomics
    ("sum",     4, jnp.float32, dict(assert_accumulate_intrinsic=True),  "intrinsic", 3),
]
from repro.core.rma import accumulate as acc_engine
for op, cnt, dtype, cfg_kw, want_path, want_phases in MATRIX:
    cfg = WindowConfig(scope="thread", max_atomic_elems=8, **cfg_kw)
    got_path = acc_engine.route(op, cnt, dtype, cfg)
    assert got_path == want_path, (op, cnt, dtype, got_path, want_path)
    def facc(x, op=op, cnt=cnt, dtype=dtype, cfg=cfg):
        win = Window.allocate(x.astype(dtype), "x", N, cfg)
        win = win.accumulate(jnp.ones((cnt,), dtype), [(0, 1)], op=op, offset=0)
        win = win.flush(stream=0)
        return win.buffer.astype(jnp.float32)
    got_phases = count_cp_n(facc, max(cnt, 8))
    print(f"accumulate op={op} count={cnt} {jnp.dtype(dtype).name}: "
          f"path={got_path} phases={got_phases}")
    assert got_phases == want_phases, (op, cnt, got_phases, want_phases)
print("accumulate path matrix OK")

# --- the declared same-op ring is the specialized path (acceptance check):
# declare_op=True keeps the ring at exactly 2(n-1) data phases; the
# undeclared baseline pays one generic-path completion ack per reduce hop
ring = {}
for declare in (True, False):
    def f(x, declare=declare):
        return rma_all_reduce(x, "x", N, order=True, declare_op=declare)
    ring[declare] = count_cp(f)[0]
    print(f"rma_all_reduce declare_op={declare}:", ring[declare])
assert ring[True] == 2 * (N - 1), "declared same-op ring = 2(n-1) data phases"
assert ring[False] == 2 * (N - 1) + (N - 1), \
    "undeclared ring pays one completion-ack phase per reduce hop"

# ...and through a lent sum-specialized dup (paper P4 x §2.3): same phases
def f_dup(x):
    win = Window.allocate(x, "x", N, WindowConfig(scope="thread", order=True,
                                                  accumulate_ops=("sum",)))
    sumwin = win.dup_with_info(same_op="sum")
    return rma_all_reduce(x, "x", N, order=True, win=sumwin)
dup_phases = count_cp(f_dup)[0]
print("rma_all_reduce via sum-specialized dup:", dup_phases)
assert dup_phases == 2 * (N - 1) + 2, \
    "lent-window ring = 2(n-1) data phases + the exit flush epoch"

# --- P5 serving (disagg acceptance): the batched page push stays at one
# data phase per page — plus the handle's [addr, epoch] header word riding
# the same packet as a second HLO ppermute — and exactly ONE thread-scoped
# flush epoch (2 phases) per batch.  Crucially NO per-page completion acks:
# adding a page costs 2 phases, never 4.
from repro.serve.paged import PagedKVWindow, PageSpec

def mk_push(k):
    spec = PageSpec(page_tokens=2, kv_heads=1, head_dim=2, n_pages=4)
    perm = [(i, (i + 1) % N) for i in range(N)]
    def f(x):
        pool = PagedKVWindow.create(spec, "x", N, dtype=jnp.float32)
        for p in range(k):
            pool = pool.alloc_page(p)
        kvs = [jnp.full((spec.page_elems,), 1.0 + p, jnp.float32)
               for p in range(k)]
        pool = pool.transfer_pages(list(range(k)), kvs, perm)
        return pool.window.buffer
    return f

push_counts = {k: count_cp(mk_push(k))[0] for k in (1, 2, 3)}
print("transfer_pages phases by batch size:", push_counts)
for k, c in push_counts.items():
    assert c == 2 * k + 2, (
        f"{k}-page batch must cost 1 data phase + 1 header word per page "
        f"+ one flush epoch (= {2*k+2}), got {c} — a per-page ack snuck in")

# --- P5 read path under P2: an ordered memhandle put→get chains on the
# stream's channel (the get cannot overtake the put), so the intermediate
# flush epoch of the unordered baseline disappears — 2 phases saved.
from repro.core.rma import DynamicWindow, memhandle_create, win_from_memhandle

def mk_ordered_get(order):
    def f(x):
        win = DynamicWindow.create_dynamic(
            x, "x", N, WindowConfig(order=order, scope="thread"),
            am_slots=1, am_msg=1)
        win = win.attach(0, offset=0, size=4)
        mh = memhandle_create(win, 0)
        mhw = win_from_memhandle(win, mh)
        mhw = mhw.put(jnp.ones((2,)), [(0, 1)], stream=0)
        if not order:
            mhw = mhw.flush(0)   # no P2: completion needed before the read
        mhw, data = mhw.get([(0, 1)], offset=0, size=2, stream=0)
        mhw = mhw.flush(0)
        return data
    return f

# Counted before optimisation: unchained, the baseline's get request header
# carries the same [addr, epoch] value over the same pairs as the put's
# header word, and XLA's CSE merges the two permutes into one — an artifact
# of the simulation (on the wire they are two packets) that would hide one
# phase of the baseline and shrink the measured saving to 1.
g_ord = count_cp(mk_ordered_get(True), optimized=False)[0]
g_unord = count_cp(mk_ordered_get(False), optimized=False)[0]
print("memhandle put->get ordered:", g_ord, " unordered baseline:", g_unord)
assert g_ord == g_unord - 2, \
    "P2 ordering must remove the put->get intermediate flush epoch"

# --- MoE dispatch acceptance: the declared one-sided all-to-all.  Per peer
# the declared exchange costs: chunks data phases + 2 (fetch_op count-header
# RTT) + 1 doorbell (intrinsic, chained under P2 — NO intermediate flush
# epoch); plus one thread-scoped exit epoch per direction stream on the
# control window.  The undeclared baseline pays, per peer, one ack RTT (the
# pre-doorbell flush, 2 phases) + the hint-less flag's software-path
# completion ack (1 phase); with accumulate-routed landings (op="sum", the
# MoE combine direction) every *chunk* additionally pays the generic-path
# per-op ack.
from repro.core.rma import rma_all_to_all

def mk_a2a(chunks, order, declare, op=None):
    def f(x):
        res = rma_all_to_all(x, "x", N, chunks=chunks, order=order,
                             declare=declare, op=op)
        return res.data
    return f

def count_a2a(f):
    g = jax.jit(compat.shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P("x")))
    txt = g.lower(jnp.zeros((N * N * 2,), jnp.float32)).compile().as_text()
    return txt.count("collective-permute(")

a2a = {}
for chunks in (1, 2):
    for declared in (True, False):
        a2a[chunks, declared] = count_a2a(mk_a2a(chunks, declared, declared))
        print(f"rma_all_to_all chunks={chunks} declared={declared}:",
              a2a[chunks, declared])
# each extra chunk costs exactly one data phase per peer — no flush epoch
# rides along with chunking
assert a2a[2, True] - a2a[1, True] == N - 1, \
    "declared all-to-all: one data phase per extra chunk per peer"
for chunks in (1, 2):
    # declared total ≤ peers·(chunks + header RTT + doorbell) + exit epochs
    # (XLA may CSE an ack leg, so assert the bound, not exact equality)
    bound = (N - 1) * (chunks + 3) + 4
    assert (N - 1) * (chunks + 3) <= a2a[chunks, True] <= bound, \
        (chunks, a2a[chunks, True], bound)
    # the baseline pays ≥ one ack RTT (2) + one software-flag ack (1) per
    # peer that the declaration elides
    saved = a2a[chunks, False] - a2a[chunks, True]
    assert saved >= 3 * (N - 1), \
        f"undeclared baseline must pay ≥3 extra phases/peer, saved={saved}"
    print(f"  declared saves {saved} phases over the baseline "
          f"(≥ {3 * (N - 1)} = 1 ack RTT + 1 flag ack per peer)")

# combine direction: undeclared accumulate landings pay one generic-path
# completion ack per *chunk* on top of the put baseline
acc_unde = count_a2a(mk_a2a(2, False, False, op="sum"))
print("rma_all_to_all op=sum undeclared (chunks=2):", acc_unde)
assert acc_unde - a2a[2, False] == (N - 1) * 2, \
    "undeclared accumulate landings cost one ack per chunk per peer"
acc_decl = count_a2a(mk_a2a(2, True, True, op="sum"))
assert acc_decl == a2a[2, True], \
    "declared accumulate landings route specialized: same phases as puts"

# --- planner acceptance: every ported consumer's compiled schedule is
# asserted phase-for-phase no worse than the hand-tuned counts measured
# above, its *prediction* brackets the measured HLO (XLA may CSE an ack leg,
# never add one), and the naive per-op-flush compile pays strictly more.
from repro.core.rma.collectives import all_reduce_plan
from repro.serve.paged import transfer_plan
from repro.core.rma.alltoall import all_to_all_plan

# ring all-reduce: planned == measured == the hand-tuned 2(n-1)
for order, hand in ((True, 2 * (N - 1)), (False, counts[False])):
    planned = all_reduce_plan("x", N, (4,), jnp.float32, order=order).phases
    naive = all_reduce_plan("x", N, (4,), jnp.float32, order=order,
                            naive_flush=True).phases
    print(f"ring plan order={order}: planned={planned} measured="
          f"{counts[order]} naive={naive}")
    assert planned == counts[order], "plan prediction must match measured HLO"
    assert planned <= hand, "planned schedule must not exceed hand-tuned"
    assert naive > planned, "naive per-op flushing must pay strictly more"

# ...including the undeclared-op and lent-window (grad-sync) shapes
assert all_reduce_plan("x", N, (4,), jnp.float32,
                       declare_op=False).phases == ring[False]
assert all_reduce_plan("x", N, (4,), jnp.float32, lent=True).phases \
    == dup_phases

# batched page push: planned == measured == 2k+2; naive pays per-page acks
for k, hand in push_counts.items():
    tp = transfer_plan(4, tuple(range(k)), 8, jnp.float32,
                       tuple((i, (i + 1) % N) for i in range(N)))
    tn = transfer_plan(4, tuple(range(k)), 8, jnp.float32,
                       tuple((i, (i + 1) % N) for i in range(N)),
                       naive_flush=True)
    assert tp.phases == hand == 2 * k + 2, (k, tp.phases, hand)
    if k > 1:
        assert tn.phases > tp.phases, "naive page push must pay per-page acks"

# all-to-all: prediction is an upper bound on measured (CSE may merge one
# ack leg) and within the hand-tuned budget; naive strictly more
for chunks in (1, 2):
    for declared in (True, False):
        pl = all_to_all_plan("x", N, (N * 2,), jnp.float32, chunks=chunks,
                             order=declared, declare=declared)
        nv = all_to_all_plan("x", N, (N * 2,), jnp.float32, chunks=chunks,
                             order=declared, declare=declared,
                             naive_flush=True)
        meas = a2a[chunks, declared]
        print(f"a2a plan chunks={chunks} declared={declared}: "
              f"planned={pl.phases} measured={meas} naive={nv.phases}")
        assert meas <= pl.phases <= meas + 1, (pl.phases, meas)
        assert nv.phases > pl.phases
print("planner acceptance (predicted vs measured vs naive) OK")

# --- two-level phase matrix: topology-declared hierarchical plans --------
# For every g×l factorization of the 8-device axis the compiled plan's
# per-tier prediction (phases_inter, phases_intra) must equal the measured
# HLO split (classify_cp parses each permute's source_target_pairs), the
# hierarchical lowerings — the grad-sync ring and the MoE op="sum" combine —
# must emit exactly 2(g-1) inter-node phases, the single-host declaration
# (1x8) must emit zero, and the degenerate factorizations (flat, 8x1) must
# reproduce the flat rows asserted above unchanged.  This is the per-tier
# upgrade of the planner-acceptance predicted==measured assertion: the
# split, not just the total, must match.
from repro.core.rma import Topology, classify_cp
from repro.core.rma.collectives import plan_all_reduce
from repro.core.rma.alltoall import plan_all_to_all

TOPOS = [None, Topology(1, 8), Topology(2, 4), Topology(4, 2),
         Topology(8, 1)]

def hlo_of(f, global_shape):
    g = jax.jit(compat.shard_map(f, mesh=mesh, in_specs=P("x"),
                                 out_specs=P("x")))
    return g.lower(jnp.zeros(global_shape, jnp.float32)).compile().as_text()

print("two-level phase matrix (grad-sync ring / MoE combine):")
for topo in TOPOS:
    label = "flat" if topo is None else f"{topo.hosts}x{topo.local}"
    hier = topo is not None and topo.hosts > 1 and topo.local > 1
    g_hosts = topo.hosts if topo is not None else N

    # grad-sync consumer shape: the non-lent plan_all_reduce ring
    def fring(x, topo=topo):
        return plan_all_reduce(x, "x", N, order=True, topology=topo)
    ring_meas = classify_cp(hlo_of(fring, (N * 8,)), topo)
    rp = all_reduce_plan("x", N, (8,), jnp.float32, order=True,
                         topology=topo)
    ring_pred = (rp.phases_inter, rp.phases_intra)

    # MoE combine consumer shape: plan_all_to_all with op="sum" landings.
    # All three outputs are consumed — with data alone, DCE strips the
    # header-window traffic (hier plans anchor it on the doorbell payload,
    # not an exit epoch) and the measured split undercounts.
    def fcomb(x, topo=topo):
        r = plan_all_to_all(x, "x", N, op="sum", topology=topo)
        return (r.data + r.counts.sum().astype(x.dtype)
                + r.bells.sum().astype(x.dtype))
    comb_meas = classify_cp(hlo_of(fcomb, (N * N * 2,)), topo)
    cp = all_to_all_plan("x", N, (N * 2,), jnp.float32, op="sum",
                         topology=topo)
    comb_pred = (cp.phases_inter, cp.phases_intra)

    print(f"  {label:>4}: ring inter/intra={ring_meas} "
          f"combine inter/intra={comb_meas}")
    # per-tier predicted == measured (satellite of the planner acceptance)
    assert ring_meas == ring_pred, (label, ring_meas, ring_pred)
    assert comb_meas == comb_pred, (label, comb_meas, comb_pred)
    # totals always equal the raw collective-permute count by construction;
    # the *flat-equivalent* rows must reproduce the flat numbers exactly
    if topo is None or topo.local == 1:
        assert ring_meas == (2 * (N - 1), 0), (label, ring_meas)
        assert comb_meas == ((N - 1) * 4 + 4, 0), (label, comb_meas)
    if hier:
        # the tentpole claim: exactly 2(g-1) inter-node phases
        assert ring_meas[0] == 2 * (g_hosts - 1), (label, ring_meas)
        assert comb_meas[0] == 2 * (g_hosts - 1), (label, comb_meas)
    if topo is not None and topo.hosts == 1:
        # single host: everything rides the shared-memory tier
        assert ring_meas[0] == 0 and comb_meas[0] == 0, (label, ring_meas,
                                                         comb_meas)
print("two-level phase matrix OK")

# --- topology-fingerprint cache regression: a factorization change must
# recompile, never replay the old schedule (the caches key on the
# fingerprint, and distinct factorizations produce distinct schedules)
r24 = all_reduce_plan("x", N, (8,), jnp.float32, order=True,
                      topology=Topology(2, 4))
r42 = all_reduce_plan("x", N, (8,), jnp.float32, order=True,
                      topology=Topology(4, 2))
assert r24 is not r42 and r24.phases_inter != r42.phases_inter
assert r24 is all_reduce_plan("x", N, (8,), jnp.float32, order=True,
                              topology=Topology(2, 4)), "cache must still hit"
c24 = all_to_all_plan("x", N, (N * 2,), jnp.float32, op="sum",
                      topology=Topology(2, 4))
c42 = all_to_all_plan("x", N, (N * 2,), jnp.float32, op="sum",
                      topology=Topology(4, 2))
assert c24 is not c42 and c24.phases_inter != c42.phases_inter
print("topology-fingerprint cache keys OK")
print("ALL HLO COUNT CHECKS PASSED")
