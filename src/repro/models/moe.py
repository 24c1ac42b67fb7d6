"""MoE block with the Outback decoupling pattern (DESIGN.md §3.3).

Placement mirrors the paper: the **router** (compute-heavy, memory-light —
one (d, E) matmul + top-k) runs where the tokens live, like the CN locator;
the **expert weights** (memory-heavy) are sharded over the ``model`` axis
like the MN pool.  With Megatron-style TP the token activations are already
replicated across ``model`` ranks, so dispatch needs **zero** communication:
each rank bins the tokens routed to its local experts (fixed capacity,
MoE-standard), runs its expert FFNs, and ONE psum recombines the weighted
outputs — a single collective phase per MoE layer, the "one round trip".

The dispatch/combine arithmetic is shared with the sharded KVS router
(``repro.core.sharded_kvs.bin_by`` is the same binning trick).

Inside ``jit`` (no shard_map) the same code runs with GSPMD-partitioned
expert weights: the einsum-based dense dispatch below keeps the HLO
collective schedule identical (weights stay sharded; one all-reduce).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.models.common import silu


def moe_params_shape(cfg):
    m = cfg.moe
    d = cfg.d_model
    shapes = {
        "router": (d, m.num_experts),
        "w_gate": (m.num_experts, d, m.d_ff_expert),
        "w_up": (m.num_experts, d, m.d_ff_expert),
        "w_down": (m.num_experts, m.d_ff_expert, d),
    }
    if m.num_shared:
        f = m.d_ff_expert * m.num_shared
        shapes.update({"s_gate": (d, f), "s_up": (d, f), "s_down": (f, d)})
    return shapes


def router_probs(p, x, cfg):
    """Top-k routing with normalized weights (mixtral/deepseek style)."""
    m = cfg.moe
    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    if m.score_func == "sigmoid":  # deepseek-v3
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(scores, m.top_k)  # (..., k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    return w, idx, scores


def load_balance_loss(scores, idx, num_experts):
    """Switch-style aux loss: E * sum(frac_tokens * frac_prob)."""
    probs_mean = jnp.mean(scores, axis=tuple(range(scores.ndim - 1)))
    onehot = jax.nn.one_hot(idx, num_experts)
    tokens_mean = jnp.mean(jnp.sum(onehot, axis=-2),
                           axis=tuple(range(onehot.ndim - 2)))
    return num_experts * jnp.sum(probs_mean * tokens_mean)


def moe_apply(p, x, cfg):
    """x (B,S,d) -> (out (B,S,d), aux_loss). Dense-dispatch formulation.

    one_hot combine keeps a static shape; with expert weights sharded
    P('model') on axis 0, GSPMD partitions the per-expert einsums and inserts
    a single all-reduce for the combine — the one-phase schedule.
    """
    B, S, d = x.shape
    m = cfg.moe
    w, idx, scores = router_probs(p, x, cfg)  # (B,S,k)
    xf = x.reshape(B * S, d)
    # dispatch matrix (tokens x experts) with combined routing weights
    comb = jnp.zeros((B * S, m.num_experts), x.dtype)
    comb = comb.at[jnp.arange(B * S)[:, None], idx.reshape(B * S, -1)].add(
        w.reshape(B * S, -1).astype(x.dtype))
    # per-expert FFN over the full token set, weighted combine.
    # capacity-factor binning (serving path) lives in moe_apply_binned.
    h_g = jnp.einsum("td,edf->tef", xf, p["w_gate"])
    h_u = jnp.einsum("td,edf->tef", xf, p["w_up"])
    h = silu(h_g) * h_u
    y = jnp.einsum("tef,efd->ted", h, p["w_down"])
    out = jnp.einsum("ted,te->td", y, comb)
    if m.num_shared:
        out = out + (silu(xf @ p["s_gate"]) * (xf @ p["s_up"])) @ p["s_down"]
    aux = load_balance_loss(scores, idx, m.num_experts)
    return out.reshape(B, S, d), aux


def moe_apply_binned(p, x, cfg, *, capacity_factor: float = 1.25):
    """Capacity-binned dispatch (the production/serving form): tokens are
    binned per expert with fixed capacity C, experts run (E, C, d) batches,
    overflow tokens fall back to zero contribution (standard drop policy)."""
    B, S, d = x.shape
    m = cfg.moe
    E, k = m.num_experts, m.top_k
    T = B * S
    C = max(8, int(T * k / E * capacity_factor))
    w, idx, scores = router_probs(p, x, cfg)
    xf = x.reshape(T, d)
    w = w.reshape(T, k)
    idx = idx.reshape(T, k)

    flat_e = idx.reshape(-1)  # (T*k,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    start = jnp.searchsorted(sorted_e, jnp.arange(E))
    pos = jnp.arange(T * k) - start[sorted_e]
    keep = pos < C
    dest = jnp.where(keep, sorted_e * C + pos, E * C)
    # token index and routing weight per bin lane
    src_tok = order // k
    lane_tok = jnp.full((E * C,), T, jnp.int32).at[dest].set(
        src_tok.astype(jnp.int32), mode="drop")
    lane_w = jnp.zeros((E * C,), x.dtype).at[dest].set(
        w.reshape(-1)[order].astype(x.dtype), mode="drop")
    safe = jnp.minimum(lane_tok, T - 1)
    xin = jnp.where((lane_tok < T)[:, None], xf[safe], 0).reshape(E, C, d)
    h = silu(jnp.einsum("ecd,edf->ecf", xin, p["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", xin, p["w_up"])
    y = jnp.einsum("ecf,efd->ecd", h, p["w_down"]).reshape(E * C, d)
    out = jnp.zeros((T + 1, d), x.dtype).at[lane_tok].add(
        y * lane_w[:, None], mode="drop")[:T]
    if m.num_shared:
        out = out + (silu(xf @ p["s_gate"]) * (xf @ p["s_up"])) @ p["s_down"]
    aux = load_balance_loss(scores, idx.reshape(B, S, k), E)
    return out.reshape(B, S, d), aux


def moe_gather_apply(p, x, cfg, stacks=None, layer_idx=None):
    """Tiny-token MoE (decode): gather ONLY the routed experts' weights.

    At T tokens x top-k, the gathered weights are (T*k) expert slices instead
    of all E experts — for mixtral long_500k (T=1, k=2, E=8) that is 4x less
    expert-weight HBM traffic per layer, and with experts replicated (E < tp)
    it avoids streaming the entire expert bank every decode step.
    """
    B, S, d = x.shape
    m = cfg.moe
    T = B * S
    w, idx, scores = router_probs(p, x, cfg)  # (B,S,k)
    xf = x.reshape(T, d)
    idx_f = idx.reshape(T * m.top_k)
    w_f = w.reshape(T * m.top_k).astype(x.dtype)
    if stacks is not None:
        # gather straight out of the layer-stacked bank: ONE gather of
        # (T*k) slices — the per-layer dynamic-slice of the whole expert
        # bank never materializes (hillclimb iteration 2, EXPERIMENTS §Perf)
        wg = stacks["w_gate"][layer_idx, idx_f]
        wu = stacks["w_up"][layer_idx, idx_f]
        wd = stacks["w_down"][layer_idx, idx_f]
    else:
        wg = p["w_gate"][idx_f]  # (T*k, d, f) — sliced reads
        wu = p["w_up"][idx_f]
        wd = p["w_down"][idx_f]
    xe = jnp.repeat(xf, m.top_k, axis=0)  # (T*k, d)
    h = silu(jnp.einsum("td,tdf->tf", xe, wg)) * jnp.einsum("td,tdf->tf", xe, wu)
    y = jnp.einsum("tf,tfd->td", h, wd) * w_f[:, None]
    out = y.reshape(T, m.top_k, d).sum(axis=1)
    if m.num_shared:
        out = out + (silu(xf @ p["s_gate"]) * (xf @ p["s_up"])) @ p["s_down"]
    aux = load_balance_loss(scores, idx, m.num_experts)
    return out.reshape(B, S, d), aux


def moe_spmd(p, x, cfg, mesh, batch_axes=None):
    """Replicated-EP dispatch under shard_map (the production path).

    Per (data, model)-device: tokens are the local data shard (replicated
    across ``model``); each model rank bins only the tokens routed to ITS
    E/M local experts, runs them, and ONE psum over ``model`` recombines —
    a single collective phase per MoE layer.  The local bin sort is over
    T_local*k elements (no cross-device sort).
    """
    import jax  # local import keeps moe importable without jax.sharding use
    from jax.sharding import PartitionSpec as P

    m_cfg = cfg.moe
    E, k = m_cfg.num_experts, m_cfg.top_k
    tp = mesh.shape["model"]
    E_loc = E // tp if E % tp == 0 else E
    if batch_axes is None:
        batch_axes = (("pod", "data") if "pod" in mesh.axis_names else "data")

    def body(x_l, router, w_gate_l, w_up_l, w_down_l, *shared):
        B_l, S, d = x_l.shape
        T = B_l * S
        C = max(8, int(T * k / E * m_cfg.capacity_factor))
        w, idx, scores = router_probs({"router": router}, x_l, cfg)
        xf = x_l.reshape(T, d)
        w = w.reshape(T * k)
        idx = idx.reshape(T * k)
        m_idx = jax.lax.axis_index("model") if E_loc != E else 0
        rel = idx - m_idx * E_loc
        local = (rel >= 0) & (rel < E_loc)
        tgt = jnp.where(local, rel, E_loc).astype(jnp.int32)
        order = jnp.argsort(tgt, stable=True).astype(jnp.int32)
        sorted_t = tgt[order]
        start = jnp.searchsorted(sorted_t, jnp.arange(E_loc, dtype=jnp.int32))
        pos = jnp.arange(T * k, dtype=jnp.int32) - start[jnp.minimum(sorted_t, E_loc - 1)]
        keep = (sorted_t < E_loc) & (pos < C)
        dest = jnp.where(keep, sorted_t * C + pos, E_loc * C)
        lane_tok = jnp.full((E_loc * C,), T, jnp.int32).at[dest].set(
            (order // k).astype(jnp.int32), mode="drop")
        lane_w = jnp.zeros((E_loc * C,), x_l.dtype).at[dest].set(
            w[order].astype(x_l.dtype), mode="drop")
        safe = jnp.minimum(lane_tok, T - 1)
        xin = jnp.where((lane_tok < T)[:, None], xf[safe], 0).reshape(E_loc, C, d)
        h = silu(jnp.einsum("ecd,edf->ecf", xin, w_gate_l)) * \
            jnp.einsum("ecd,edf->ecf", xin, w_up_l)
        y = jnp.einsum("ecf,efd->ecd", h, w_down_l).reshape(E_loc * C, d)
        out = jnp.zeros((T + 1, d), x_l.dtype).at[lane_tok].add(
            y * lane_w[:, None], mode="drop")[:T]
        shared_out = 0
        if shared:
            s_gate, s_up, s_down = shared
            shared_out = (silu(xf @ s_gate) * (xf @ s_up)) @ s_down
        if E_loc != E:
            # shared-expert partials (row-split) fold into the same psum as
            # the routed combine when sharded; otherwise add post-psum.
            if shared and shared_sharded:
                out = jax.lax.psum(out + shared_out, "model")
            else:
                out = jax.lax.psum(out, "model") + shared_out
        else:
            out = out + shared_out
        aux = load_balance_loss(scores, idx.reshape(B_l, S, k), E)
        return out.reshape(B_l, S, d), aux[None]

    ep = P("model", None, None) if E % tp == 0 and tp > 1 else P(None, None, None)
    shared_args, shared_specs = (), ()
    shared_sharded = False
    if m_cfg.num_shared:
        fs = m_cfg.d_ff_expert * m_cfg.num_shared
        shared_sharded = tp > 1 and fs % tp == 0
        col = P(None, "model") if shared_sharded else P(None, None)
        row = P("model", None) if shared_sharded else P(None, None)
        shared_args = (p["s_gate"], p["s_up"], p["s_down"])
        shared_specs = (col, col, row)
    ba = batch_axes
    ba_t = (ba,) if isinstance(ba, str) else ba
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(ba, None, None), P(None, None), ep, ep, ep,
                  *shared_specs),
        out_specs=(P(ba, None, None), P(ba_t)))
    out, aux = fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"],
                  *shared_args)
    return out, jnp.mean(aux)
