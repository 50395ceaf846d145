"""Mixture-of-Experts: top-k routing with sort-based capacity dispatch.

Dispatch strategy (pure JAX, GSPMD/EP-friendly):

1. route: logits (T, E) → top-k expert ids + renormalized gates.
2. sort the T·k assignments by expert id; compute each assignment's rank
   within its expert (position = index − searchsorted(start of expert)).
3. scatter tokens into a dense (E, C, d) buffer (capacity C, drop beyond) —
   the buffer is the *expert-parallel* tensor: sharded over the "expert"
   logical axis, so GSPMD inserts the all-to-all exchange exactly where the
   RMA layer's pre-registered expert windows sit on real hardware.
4. batched expert matmuls (E, C, d)·(E, d, ff) — MXU-shaped.
5. gather back to token order and combine with gate weights.

``ep_mode="rma"`` (``MoEConfig.ep_mode`` or the ``moe_apply`` override)
replaces step 3's partitioner-inserted exchange with the explicit one-sided
path: tokens are sharded over the expert axis inside ``shard_map``, each
device packs its assignments per *destination device* (first-level sort),
dispatch rides :func:`repro.core.rma.alltoall.plan_all_to_all` (per-peer
chunked puts + fetch_op count headers + P2-chained doorbells), receivers run
the second-level sort into their local ``(E/n, C, d)`` buffer, and the
combine returns through the same collective with ``op="sum"`` — every
landing an accumulate routed through the op-specialized engine on a
sum-declared view.  See ``docs/moe_ep.md``.

Shared experts (DeepSeek-style) are dense SwiGLU applied to every token.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers
from repro.sharding import current_rules, logical_constraint

Array = jax.Array


def init_moe(key, cfg) -> dict:
    """Router over every expert; ``wi``/``wo`` of the held experts only
    (``MoEConfig.experts_held``; all of them by default)."""
    mo = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p = {
        "router": layers.trunc_normal(ks[0], (d, mo.num_experts), 1.0, jnp.float32),
        "wi": layers.trunc_normal(ks[1], (mo.n_held, d, 2 * mo.d_ff_expert), 1.0,
                                  cfg.param_dtype),
        "wo": layers.trunc_normal(ks[2], (mo.n_held, mo.d_ff_expert, d), 1.0,
                                  cfg.param_dtype),
    }
    if mo.n_shared:
        p["shared"] = layers.init_swiglu(ks[3], d, mo.d_ff_shared, cfg.param_dtype)
    return p


def moe_spec(cfg) -> dict:
    p = {
        "router": ("embed", None),
        "wi": ("expert", "embed", "mlp_expert"),
        "wo": ("expert", "mlp_expert", "embed"),
    }
    if cfg.moe.n_shared:
        p["shared"] = layers.swiglu_spec()
    return p


def moe_apply(params: dict, x: Array, cfg, *, return_aux: bool = False,
              ep_mode: str | None = None):
    """Apply the MoE layer to ``x`` (B, S, d).  Returns (out, aux_loss).

    ``ep_mode``: per-call override of ``cfg.moe.ep_mode`` — ``"gspmd"``
    (partitioner-inserted all-to-all at the sharded dispatch buffer) or
    ``"rma"`` (explicit one-sided exchange inside ``shard_map`` over the
    expert axis; falls back to the single-device code path when no sharding
    rules are active or the expert axis has size 1)."""
    mode = ep_mode if ep_mode is not None else getattr(cfg.moe, "ep_mode", "gspmd")
    if mode not in ("gspmd", "rma"):
        raise ValueError(f"unknown ep_mode {mode!r}; expected 'gspmd' or 'rma'")
    if mode == "rma":
        return _moe_apply_rma(params, x, cfg)
    if cfg.moe.experts_held is not None:
        out, aux, _ = moe_apply_held(params, x, cfg)
        return out, aux
    mo = cfg.moe
    B, S, d = x.shape
    dt = x.dtype
    T = B * S
    E, k = mo.num_experts, mo.top_k
    xt = x.reshape(T, d)

    # --- routing (fp32 for numerics) ---------------------------------------
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = lax.top_k(probs, k)  # (T, k)
    if mo.renorm_gates:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # load-balancing auxiliary loss (Switch-style)
    density = jnp.zeros((E,), jnp.float32).at[eidx.reshape(-1)].add(1.0) / (T * k)
    mean_prob = probs.mean(axis=0)
    aux = E * jnp.sum(density * mean_prob)

    # --- sort-based dispatch -------------------------------------------------
    C = mo.capacity(T)
    flat_e = eidx.reshape(-1)                       # (T*k,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    tok_of = order // k
    starts = jnp.searchsorted(sorted_e, jnp.arange(E))
    pos_in_e = jnp.arange(T * k) - starts[sorted_e]
    keep = pos_in_e < C
    dest = jnp.where(keep, sorted_e * C + pos_in_e, E * C)  # OOB = dropped

    buf = jnp.zeros((E * C, d), dt).at[dest].set(xt[tok_of], mode="drop")
    buf = buf.reshape(E, C, d)
    # EP over "expert" (model axis) × feature dim over "fsdp"/data: the
    # 2D-sharded dispatch measured best — §Perf D2/D2' tried expert-only
    # (16x compute replication) and expert×capacity (GSPMD materializes the
    # scatter: 264 GiB/dev peak, 9x collective bytes); both refuted.
    buf = logical_constraint(buf, "expert", None, "embed")

    # --- expert computation (batched, MXU-shaped) ----------------------------
    h = jnp.einsum("ecd,edf->ecf", buf, params["wi"].astype(dt))
    gate_h, up_h = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(gate_h.astype(jnp.float32)).astype(dt) * up_h
    yb = jnp.einsum("ecf,efd->ecd", h, params["wo"].astype(dt))
    yb = logical_constraint(yb, "expert", None, "embed")

    # --- combine -----------------------------------------------------------
    y_flat = yb.reshape(E * C, d)
    safe_dest = jnp.where(keep, dest, 0)
    y_sorted = y_flat[safe_dest] * keep[:, None].astype(dt)
    gates_sorted = gates.reshape(-1)[order].astype(dt)
    out = jnp.zeros((T, d), dt).at[tok_of].add(y_sorted * gates_sorted[:, None])

    if mo.n_shared:
        out = out + layers.swiglu(xt, params["shared"])

    out = out.reshape(B, S, d)
    if return_aux:
        return out, aux
    return out, aux


# ---------------------------------------------------------------------------
# an expert share: the experts this chip holds, dropless
# ---------------------------------------------------------------------------

#: rows of one tile of the grouped expert matmul (the MXU's 128)
HELD_TILE = 128


def _grouped_swiglu(xs: Array, counts: Array, starts: Array, wi: Array,
                    wo: Array, tm: int) -> Array:
    """SwiGLU experts over rows grouped by expert: rows ``starts[e] …
    starts[e] + counts[e] − 1`` of ``xs`` (R, d) go through expert ``e``.
    Each expert runs ``ceil(counts[e] / tm)`` tiles of ``tm`` rows (dynamic
    trip counts), so an expert no row chose is never read and no row is
    dropped.  Returns (R, d) in ``xs``' dtype; rows past every group are
    zero."""
    R, d = xs.shape
    dt = xs.dtype
    xs = jnp.concatenate([xs, jnp.zeros((tm, d), dt)])     # tiles never clamp

    def expert(e, out):
        def tile(t, out):
            r0 = starts[e] + t * tm
            h = jnp.einsum("td,df->tf", lax.dynamic_slice_in_dim(xs, r0, tm),
                           wi[e].astype(dt))
            gate, up = jnp.split(h, 2, axis=-1)
            h = jax.nn.silu(gate.astype(jnp.float32)).astype(dt) * up
            y = jnp.einsum("tf,fd->td", h, wo[e].astype(dt))
            valid = (t * tm + jnp.arange(tm) < counts[e])[:, None]
            old = lax.dynamic_slice_in_dim(out, r0, tm)
            return lax.dynamic_update_slice_in_dim(
                out, jnp.where(valid, y, old), r0, axis=0)

        return lax.fori_loop(0, (counts[e] + tm - 1) // tm, tile, out)

    out = lax.fori_loop(0, wi.shape[0], expert, jnp.zeros((R + tm, d), dt))
    return out[:R]


def moe_apply_held(params: dict, x: Array, cfg):
    """The MoE layer of a chip that holds the experts
    ``cfg.moe.experts_held = (first, stop)`` of every layer (expert
    parallelism's share, run without its exchange).

    The router keeps all ``num_experts`` outputs and its top-k over all of
    them; the layer adds the held experts' part of the routed sum
    (gate-weighted, gates as the router gives them) and the shared experts.
    Held assignments are sorted by expert and run through a grouped matmul
    (:func:`_grouped_swiglu`) with **no capacity**: nothing is dropped,
    however skewed the routing.  Serving only: the dynamic trip counts are
    not reverse-differentiable.

    Returns ``(out, aux, counts)``: ``counts`` (2,) int32 is the
    assignments routed to held experts and the held experts with at least
    one of them."""
    mo = cfg.moe
    B, S, d = x.shape
    dt = x.dtype
    T = B * S
    E, k = mo.num_experts, mo.top_k
    first, stop = mo.experts_held
    n = stop - first
    xt = x.reshape(T, d)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = lax.top_k(probs, k)  # (T, k)
    if mo.renorm_gates:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    density = jnp.zeros((E,), jnp.float32).at[eidx.reshape(-1)].add(1.0) / (T * k)
    aux = E * jnp.sum(density * probs.mean(axis=0))

    local = eidx.reshape(-1) - first
    flat = jnp.where((local >= 0) & (local < n), local, n)   # n: not held
    order = jnp.argsort(flat, stable=True)
    sorted_e = flat[order]
    tok_of = order // k
    counts = jnp.zeros((n + 1,), jnp.int32).at[flat].add(1)[:n]
    starts = jnp.cumsum(counts) - counts
    tm = min(HELD_TILE, -(-T * k // 8) * 8)
    ys = _grouped_swiglu(xt[tok_of], counts, starts, params["wi"],
                         params["wo"], tm)
    w = jnp.where(sorted_e < n, gates.reshape(-1)[order], 0.0)
    out = jnp.zeros((T, d), jnp.float32).at[tok_of].add(
        ys.astype(jnp.float32) * w[:, None])
    if mo.n_shared:
        out = out + layers.swiglu(xt, params["shared"]).astype(jnp.float32)
    stats = jnp.stack([counts.sum(), (counts > 0).sum()]).astype(jnp.int32)
    return out.astype(dt).reshape(B, S, d), aux, stats


# ---------------------------------------------------------------------------
# ep_mode="rma": explicit expert parallelism on the one-sided substrate
# ---------------------------------------------------------------------------


def _ep_axis() -> tuple[str | None, int]:
    """The mesh axis the "expert" logical name maps to under the active
    sharding rules, and its size.  ``(None, 1)`` when no rules are active,
    the name is unmapped, or the axis is trivial — the degenerate
    single-device path (same dispatch code, no communication)."""
    rules = current_rules()
    if rules is None:
        return None, 1
    v = rules.rules.get("expert")
    axis = v if isinstance(v, str) else (v[0] if v else None)
    if axis is None:
        return None, 1
    n = rules.mesh.shape[axis]
    return (axis, n) if n > 1 else (None, 1)


def _pair_capacity(mo, tokens_local: int, n: int) -> int:
    """Row capacity of one (source device → destination device) exchange
    chunk: the expected per-peer share of the local assignments scaled by
    the capacity factor, rounded up to 8 for tiling and capped at the
    all-assignments-to-one-peer worst case.

    This is a drop layer the GSPMD path does not have (its only bound is the
    per-expert capacity): under a *tight* ``capacity_factor`` with heavily
    skewed routing, the rma path can drop assignments at the exchange that
    gspmd would still deliver — the standard EP exchange-buffer trade
    (bounded per-peer bandwidth in return).  With the ample factors the
    parity tests use, this cap never binds (it is ≥ the expected share by
    the same margin as the expert capacity)."""
    c = math.ceil(tokens_local * mo.top_k * mo.capacity_factor / n)
    return min(tokens_local * mo.top_k, max(8, -(-c // 8) * 8))


def _moe_ep_shard(params: dict, xt: Array, cfg, *, axis: str | None, n: int,
                  t_valid: int | None = None):
    """Per-device MoE over this shard's tokens ``xt`` (Tl, d), expert-
    parallel over ``axis``: route → first-level (per-peer) sort →
    ``plan_all_to_all`` dispatch (a compiled-plan replay) → second-level
    (per-local-expert) sort →
    expert matmuls → ``op="sum"`` all-to-all combine → gate-weighted merge.
    Runs inside ``shard_map`` when ``n > 1``; with ``n == 1`` the exchanges
    are identity and the two sort levels compose to the GSPMD path's single
    sort.  ``t_valid``: global count of real tokens — rows past it are
    divisibility padding and are excluded from routing statistics, dispatch
    and capacity."""
    from repro.core.rma.alltoall import plan_all_to_all
    from repro.core.rma.topology import default_topology

    topo = default_topology(n) if n > 1 else None
    mo = cfg.moe
    ep_backend = getattr(mo, "ep_backend", "rma")
    if ep_backend not in ("auto", "rma", "gspmd"):
        raise ValueError(
            f"ep_backend={ep_backend!r} invalid for in-mesh dispatch; "
            "expected 'auto', 'rma', or 'gspmd'")
    Tl, d = xt.shape
    E, k = mo.num_experts, mo.top_k
    E_local = E // n
    rank = lax.axis_index(axis) if n > 1 else jnp.int32(0)
    T = Tl * n if t_valid is None else t_valid
    padded = t_valid is not None and t_valid != Tl * n
    tok_ok = (rank * Tl + jnp.arange(Tl) < T if padded
              else jnp.ones((Tl,), bool))

    # --- routing (fp32), aux from global statistics ------------------------
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = lax.top_k(probs, k)
    if mo.renorm_gates:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    w = tok_ok.astype(jnp.float32)
    density = jnp.zeros((E,), jnp.float32).at[eidx.reshape(-1)].add(
        jnp.repeat(w, k))
    prob_sum = (probs * w[:, None]).sum(axis=0)
    if n > 1:
        density = lax.psum(density, axis)
        prob_sum = lax.psum(prob_sum, axis)
    aux = E * jnp.sum((density / (T * k)) * (prob_sum / T))

    # --- first-level sort: pack assignments per destination device ---------
    Cp = _pair_capacity(mo, Tl, n)
    flat_e = eidx.reshape(-1)                      # (Tl*k,)
    dd = flat_e // E_local                         # owning device per assignment
    if padded:
        dd = jnp.where(jnp.repeat(tok_ok, k), dd, n)   # pad rows sort last
    send_order = jnp.argsort(dd, stable=True)
    sorted_dd = dd[send_order]
    tok_of = send_order // k
    starts = jnp.searchsorted(sorted_dd, jnp.arange(n + 1))
    pos_in_d = jnp.arange(Tl * k) - starts[sorted_dd]
    keep_s = (pos_in_d < Cp) & (sorted_dd < n)
    slot = jnp.where(keep_s, sorted_dd * Cp + pos_in_d, n * Cp)  # OOB = drop
    send_counts = jnp.minimum(starts[1:] - starts[:-1], Cp).astype(jnp.int32)
    # payload rows: [token features | local expert id] — the id rides the
    # exchange so the receiver can run its second-level dispatch.  The wire
    # dtype is the model dtype (same bytes the GSPMD dispatch buffer moves);
    # the id column must stay exactly representable, so wide expert counts
    # fall back to f32 (bf16 holds integers to 256, f16 to 2048).
    id_exact = {jnp.dtype(jnp.bfloat16): 256, jnp.dtype(jnp.float16): 2048}
    wire_dt = (jnp.float32
               if E_local > id_exact.get(jnp.dtype(xt.dtype), 2 ** 24)
               else xt.dtype)
    eid_local = (flat_e % E_local)[send_order].astype(wire_dt)
    rows = jnp.concatenate(
        [xt[tok_of].astype(wire_dt), eid_local[:, None]], axis=-1)
    payload = jnp.zeros((n * Cp, d + 1), wire_dt
                        ).at[slot].set(rows, mode="drop")

    # --- dispatch: declared one-sided all-to-all ---------------------------
    if n > 1:
        res = plan_all_to_all(payload, axis, n, counts=send_counts,
                              order=True, declare=True, topology=topo,
                              backend=ep_backend)
        recv, recv_counts = res.data, res.counts
    else:
        recv, recv_counts = payload, send_counts

    # --- second-level sort: received rows → local (E_local, C, d) buffer ---
    C = mo.capacity(T)
    slot_src = jnp.arange(n * Cp) // Cp
    valid = (jnp.arange(n * Cp) % Cp) < recv_counts[slot_src]
    re = jnp.where(valid, recv[:, d].astype(jnp.int32), E_local)  # sentinel
    order2 = jnp.argsort(re, stable=True)
    sorted_re = re[order2]
    starts2 = jnp.searchsorted(sorted_re, jnp.arange(E_local + 1))
    pos2 = jnp.arange(n * Cp) - starts2[jnp.minimum(sorted_re, E_local)]
    keep2 = (sorted_re < E_local) & (pos2 < C)
    dest2 = jnp.where(keep2, sorted_re * C + pos2, E_local * C)
    buf = jnp.zeros((E_local * C, d), jnp.float32
                    ).at[dest2].set(recv[order2, :d], mode="drop")
    buf = buf.reshape(E_local, C, d)

    # --- local expert computation ------------------------------------------
    # wi/wo arrive already sliced to this device's experts: the shard_map
    # in_specs split them over the expert dim (true expert-parallel memory —
    # no device materializes the full expert tensors); the n == 1 direct
    # call passes the full arrays, which are the local slice by definition.
    dt = xt.dtype
    wi, wo = params["wi"], params["wo"]
    h = jnp.einsum("ecd,edf->ecf", buf.astype(dt), wi.astype(dt))
    gate_h, up_h = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(gate_h.astype(jnp.float32)).astype(dt) * up_h
    yb = jnp.einsum("ecf,efd->ecd", h, wo.astype(dt)).astype(jnp.float32)

    # --- gather back to exchange-slot order and return to the origins ------
    y_flat = yb.reshape(E_local * C, d)
    y_sorted = y_flat[jnp.where(keep2, dest2, 0)] * keep2[:, None]
    y_back = jnp.zeros((n * Cp, d), wire_dt
                       ).at[order2].set(y_sorted.astype(wire_dt))
    if n > 1:
        back = plan_all_to_all(y_back, axis, n, counts=recv_counts,
                               op="sum", order=True, declare=True,
                               topology=topo, backend=ep_backend)
        y_ret = back.data
    else:
        y_ret = y_back

    # --- combine: the origin weighs each assignment's result by its gate ---
    y_assign = (y_ret[jnp.where(keep_s, slot, 0)].astype(jnp.float32)
                * keep_s[:, None])
    gates_sorted = gates.reshape(-1)[send_order]
    out = jnp.zeros((Tl, d), jnp.float32
                    ).at[tok_of].add(y_assign * gates_sorted[:, None])
    return out.astype(xt.dtype), aux


def _moe_apply_rma(params: dict, x: Array, cfg):
    """The ``ep_mode="rma"`` entry: shard tokens over the expert axis and run
    :func:`_moe_ep_shard` inside ``shard_map`` (the single-device fallback
    calls it directly)."""
    from jax.sharding import PartitionSpec as P

    from repro import compat

    mo = cfg.moe
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    axis, n = _ep_axis()
    if n > 1 and mo.num_experts % n:
        raise ValueError(
            f"ep_mode='rma' needs num_experts={mo.num_experts} divisible by "
            f"the expert-axis size {n}")
    if n == 1:
        out, aux = _moe_ep_shard(params, xt, cfg, axis=None, n=1)
    else:
        pad = (-T) % n
        if pad:
            xt_in = jnp.concatenate(
                [xt, jnp.zeros((pad, d), xt.dtype)], axis=0)
        else:
            xt_in = xt
        rules = current_rules()
        # router replicated; expert tensors split over the expert dim so each
        # device holds only its E/n experts' weights (expert-parallel memory)
        pspecs = jax.tree.map(lambda _: P(), params)
        pspecs["wi"] = pspecs["wo"] = P(axis)
        fn = lambda p, t: _moe_ep_shard(p, t, cfg, axis=axis, n=n, t_valid=T)
        out, aux = compat.shard_map(
            fn, mesh=rules.mesh, in_specs=(pspecs, P(axis)),
            out_specs=(P(axis), P()))(params, xt_in)
        out = out[:T]
    if mo.n_shared:
        out = out + layers.swiglu(xt, params["shared"])
    return out.reshape(B, S, d), aux


def moe_ref(params: dict, x: Array, cfg) -> Array:
    """Oracle: dense per-token loop over selected experts (no capacity drops;
    only the held experts' part where ``experts_held`` says so).

    Used by property tests: when capacity is ample, ``moe_apply`` must match.
    """
    mo = cfg.moe
    B, S, d = x.shape
    xt = x.reshape(-1, d)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = lax.top_k(probs, mo.top_k)
    if mo.renorm_gates:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    out = jnp.zeros_like(xt, dtype=jnp.float32)
    first, stop = mo.experts_held or (0, mo.num_experts)
    for e in range(first, stop):
        wi, wo = params["wi"][e - first], params["wo"][e - first]
        h = xt @ wi.astype(xt.dtype)
        g, u = jnp.split(h, 2, axis=-1)
        y = (jax.nn.silu(g.astype(jnp.float32)).astype(xt.dtype) * u) @ wo.astype(xt.dtype)
        w_e = jnp.where(eidx == e, gates, 0.0).sum(-1)  # (T,)
        out = out + y.astype(jnp.float32) * w_e[:, None]
    if mo.n_shared:
        out = out + layers.swiglu(xt, params["shared"]).astype(jnp.float32)
    return out.reshape(B, S, d).astype(x.dtype)


__all__ = ["init_moe", "moe_spec", "moe_apply", "moe_apply_held", "moe_ref"]
