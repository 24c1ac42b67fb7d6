"""Distributed Outback over a device mesh: the paper's pools as mesh axes.

Placement (mesh ``(data=D, model=M)``):

* shard ``m``'s **CN component** (Othello + seeds) is replicated down mesh
  column ``m`` — every device in the column is one of the shard's compute
  nodes caching the locator (paper: "each compute node is allocated a memory
  budget for caching the compute-heavy component");
* shard ``m``'s **MN component** (DMPH buckets + heap) is *range-sharded over
  the column's D devices* — the column jointly plays the shard's memory node,
  so KVS capacity scales with the whole mesh.  The heap is re-ordered at
  build time so every bucket's KV blocks live on the bucket's own row
  (one-touch locality, mirroring the paper's single-MN address space).

A batched Get is exactly the paper's message flow, with collectives as the
network:

  0. (optional) CN-cache probe: each device probes its ``ShardedCNCache``
     replica (``repro.core.cn_cache``); hit lanes are answered locally and
     marked with an out-of-range bin target so they never enter the routing
     bins — under zipfian skew most of the batch stops here;
  1. service-layer routing: bin by key-shard, ``all_to_all`` over ``model``
     (the paper's front-end forwarding — not an index round trip);
  2. CN compute on the receiving device: Othello + seeds -> (bucket, slot);
  3. **the one round trip**: bin by bucket range, ``all_to_all`` over
     ``data`` carrying (bucket, slot); the owning sub-MN performs two pure
     gathers (slot word, heap block) — zero hashes, zero compares;
  4. response ``all_to_all``s retrace the route; the CN full-key check runs
     at the origin.

``variant='race'`` is the one-sided baseline on the same substrate: TWO
dependent gather phases over ``data`` (bucket-group fetch, CN-side slot
selection, then heap fetch) — 2 round trips and ~3x the on-wire bytes, all
visible in the lowered HLO for the roofline comparison.

Routing uses fixed per-bin capacity (MoE-style) so shapes stay static; empty
lanes carry the sentinel key so no separate validity tensor crosses the wire.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import ludo, slots
from repro.core.cn_cache import ShardedCNCache, cache_probe
from repro.core.hashing import hash64_32, slot_hash, split_u64
from repro.core.meter import CommMeter
from repro.core.outback import (GET_REQ_BYTES, KV_BLOCK_BYTES, OutbackShard,
                                meter_cache_batch)

_ROUTE_SEED = 0x50A7ED
SENT = 0xFFFFFFFF  # sentinel key lane (no real key hashes to all-ones twice)


@dataclasses.dataclass
class ShardedKVSState:
    """Stacked host arrays for M shards, ready to be device_put on a mesh."""

    # CN component, replicated over 'data': specs P('model', ...)
    words_a: np.ndarray  # (M, WA)
    words_b: np.ndarray  # (M, WB)
    seeds: np.ndarray  # (M, NB)
    oth_meta: np.ndarray  # (M, 4) uint32: seed_a, seed_b (per-shard retries)
    # MN component, range-sharded over 'data': specs P('model', 'data', ...)
    slots_lo: np.ndarray  # (M, NB, 4)
    slots_hi: np.ndarray  # (M, NB, 4)
    heap_klo: np.ndarray  # (M, CAP)
    heap_khi: np.ndarray
    heap_vlo: np.ndarray
    heap_vhi: np.ndarray
    num_buckets: int  # per shard (padded to a multiple of D)
    heap_cap: int  # per shard (padded to a multiple of D)
    ma: int  # othello geometry, equal across shards
    mb: int
    # transport seam: set by build_sharded(transport=...); make_get_fn then
    # returns a host wrapper that meters every batched Get into it, putting
    # the mesh path on the same simulated clock as the scalar protocols
    meter: CommMeter | None = None
    # the host-side OutbackShard objects the state was stacked from, kept
    # only when build_sharded(keep_shards=True): the repro.api 'sharded'
    # adapter serves the full protocol (incl. mutations) through them and
    # re-installs dirty shards before handing the state to the mesh path
    shards: list | None = None

    def arrays(self):
        return (self.words_a, self.words_b, self.seeds, self.oth_meta,
                self.slots_lo, self.slots_hi, self.heap_klo, self.heap_khi,
                self.heap_vlo, self.heap_vhi)

    def array_specs(self):
        cn = P("model")
        mn = P("model", "data")
        return (cn, cn, cn, cn, mn, mn, mn, mn, mn, mn)

    def index_bytes_cn(self) -> int:
        return self.words_a.nbytes + self.words_b.nbytes + self.seeds.nbytes

    def index_bytes_mn(self) -> int:
        return self.slots_lo.nbytes + self.slots_hi.nbytes


def build_sharded(keys: np.ndarray, values: np.ndarray, *, num_shards: int,
                  data_parallel: int, load_factor: float = 0.85,
                  heap_slack: float = 1.5, rng_seed: int = 0,
                  transport=None, keep_shards: bool = False) -> ShardedKVSState:
    """Partition keys into ``num_shards`` equal-geometry Outback shards and
    stack their components for mesh placement (heap co-located per row).

    With ``transport`` (a ``repro.net.Transport``), the state carries a
    CommMeter sinking into it and ``make_get_fn`` meters each batched Get;
    the default ``None`` leaves the mesh path exactly as before.

    ``keep_shards=True`` retains the host ``OutbackShard`` objects on
    ``state.shards`` (their meters sink into ``transport`` too) so the
    ``repro.api`` adapter can serve scalar protocol ops and mutations and
    re-stack mutated shards; the default discards them as before."""
    keys = np.asarray(keys, dtype=np.uint64)
    values = np.asarray(values, dtype=np.uint64)
    lo, hi = split_u64(keys)
    shard_of = hash64_32(lo, hi, _ROUTE_SEED) % np.uint32(num_shards)

    n_max = max(int((shard_of == m).sum()) for m in range(num_shards))
    D = data_parallel
    nb = _round_up(max(D, int(np.ceil(n_max / (4.0 * load_factor)))), D)
    cap = _round_up(int(np.ceil(n_max * heap_slack)) + 4 * D, D)
    ma = int(np.ceil(1.33 * n_max)) + 7
    mb = int(np.ceil(1.00 * n_max)) + 11

    M = num_shards
    wa_words = (ma + 31) // 32
    wb_words = (mb + 31) // 32
    meter = None
    if transport is not None:
        meter = CommMeter()
        meter.sink = transport
    st = ShardedKVSState(
        meter=meter,
        words_a=np.zeros((M, wa_words), np.uint32),
        words_b=np.zeros((M, wb_words), np.uint32),
        seeds=np.zeros((M, nb), np.uint8),
        oth_meta=np.zeros((M, 4), np.uint32),
        slots_lo=np.zeros((M, nb, 4), np.uint32),
        slots_hi=np.zeros((M, nb, 4), np.uint32),
        heap_klo=np.full((M, cap), SENT, np.uint32),
        heap_khi=np.full((M, cap), SENT, np.uint32),
        heap_vlo=np.zeros((M, cap), np.uint32),
        heap_vhi=np.zeros((M, cap), np.uint32),
        num_buckets=nb, heap_cap=cap, ma=ma, mb=mb)

    kept = [] if keep_shards else None
    for m in range(M):
        mask = shard_of == m
        sh = OutbackShard(keys[mask], values[mask], load_factor=load_factor,
                          rng_seed=rng_seed + m, num_buckets=nb,
                          oth_ma=ma, oth_mb=mb)
        _install_shard(st, m, sh, D)
        if kept is not None:
            sh.meter.sink = transport
            kept.append(sh)
    st.shards = kept
    return st


def _install_shard(st: ShardedKVSState, m: int, sh: OutbackShard, D: int) -> None:
    """Copy one shard into the stacked state, re-ordering its heap so each
    bucket row's blocks live in that row's heap range."""
    oth = sh.cn.othello
    st.words_a[m, : oth.words_a.shape[0]] = oth.words_a
    st.words_b[m, : oth.words_b.shape[0]] = oth.words_b
    st.seeds[m] = sh.cn.seeds
    st.oth_meta[m] = (oth.seed_a, oth.seed_b, 0, 0)

    nb, cap = st.num_buckets, st.heap_cap
    per_row = cap // D
    lens = slots.unpack_len(sh.slots_hi)
    b_idx, s_idx = np.nonzero(lens != 0)
    old_addr = sh.slots_lo[b_idx, s_idx].astype(np.int64)
    rows = (b_idx // (nb // D)).astype(np.int64)
    order = np.argsort(rows, kind="stable")
    rows_s = rows[order]
    start = np.searchsorted(rows_s, np.arange(D))
    pos = np.arange(rows_s.size) - start[rows_s]
    if pos.size and int(pos.max()) >= per_row:
        raise ValueError("heap row overflow; raise heap_slack")
    new_addr = rows_s * per_row + pos

    st.heap_klo[m, new_addr] = sh.heap_klo[old_addr[order]]
    st.heap_khi[m, new_addr] = sh.heap_khi[old_addr[order]]
    st.heap_vlo[m, new_addr] = sh.heap_vlo[old_addr[order]]
    st.heap_vhi[m, new_addr] = sh.heap_vhi[old_addr[order]]
    st.slots_lo[m] = sh.slots_lo
    st.slots_hi[m] = sh.slots_hi
    st.slots_lo[m, b_idx[order], s_idx[order]] = new_addr.astype(np.uint32)


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


# ---------------------------------------------------------------------------
# routing helpers (MoE-style fixed-capacity binning)


def bin_by(tgt: jnp.ndarray, nbins: int, cap: int):
    """Map a (B,)-batch to (nbins*cap,) bin lanes.

    Returns ``idxmap`` (nbins*cap,) int32 of source positions (== B for empty
    lanes): gather through it to fill bins, scatter through it to un-bin.
    Positions with ``tgt >= nbins`` never enter any bin (the CN-cache probe
    stage marks its hits this way so they skip the round trip entirely).
    """
    B = tgt.shape[0]
    tgt = tgt.astype(jnp.int32)
    order = jnp.argsort(tgt, stable=True).astype(jnp.int32)
    sorted_tgt = tgt[order]
    start = jnp.searchsorted(sorted_tgt, jnp.arange(nbins, dtype=jnp.int32))
    in_range = sorted_tgt < nbins
    pos = jnp.arange(B, dtype=jnp.int32) - start[
        jnp.minimum(sorted_tgt, nbins - 1)].astype(jnp.int32)
    dest = jnp.where((pos < cap) & in_range, sorted_tgt * cap + pos,
                     nbins * cap)
    idxmap = jnp.full((nbins * cap,), B, dtype=jnp.int32)
    idxmap = idxmap.at[dest].set(order, mode="drop")
    return idxmap


def take(arr, idxmap, fill):
    """Gather rows with sentinel fill for empty lanes (idx == B)."""
    B = arr.shape[0]
    safe = jnp.minimum(idxmap, B - 1)
    mask = (idxmap < B).reshape(idxmap.shape + (1,) * (arr.ndim - 1))
    return jnp.where(mask, arr[safe], jnp.asarray(fill, arr.dtype))


def unbin(idxmap, binned, out_len, fill=0):
    """Scatter bin lanes back to original positions."""
    tmpl = jnp.full((out_len + 1, *binned.shape[1:]), fill, binned.dtype)
    return tmpl.at[idxmap].set(binned, mode="drop")[:out_len]


def _a2a(x, axis):
    return jax.lax.all_to_all(x, axis, 0, 0, tiled=False)


# ---------------------------------------------------------------------------
# the SPMD Get programs


def make_get_fn(mesh: Mesh, st: ShardedKVSState, batch_per_device: int,
                *, capacity_slack: float = 2.0, variant: str = "outback",
                cache: ShardedCNCache | None = None):
    """Build the jitted SPMD batched-Get for this mesh/state geometry.

    ``variant``: 'outback' (1 index RT) or 'race' (2 dependent index RTs,
    the one-sided analogue).  Returns (jitted_fn, (cap_m, cap_d)).

    With ``cache`` (one CN-cache replica per device, see ``place_cache``),
    every device probes its replica *before* the routing pair: hit lanes
    are answered locally, marked with an out-of-range shard target so they
    never enter the routing bins, and merged back at the end.  The fn then
    takes ``(q_lo, q_hi, *cache_arrays, *state_arrays)`` and also returns
    the per-lane hit mask (for host-side adaptation/accounting).
    """
    D = int(mesh.shape["data"])
    M = int(mesh.shape["model"])
    cap_m = _round_up(int(np.ceil(batch_per_device / max(M, 1) * capacity_slack)) + 1, 8)
    cap_d = _round_up(int(np.ceil(cap_m * M / max(D, 1) * capacity_slack)) + 1, 8)
    nb_per_row = st.num_buckets // D
    heap_per_row = st.heap_cap // D
    nb, ma, mb = st.num_buckets, st.ma, st.mb

    def cn_locate(q_lo, q_hi, words_a, words_b, seeds, oth_meta):
        ia = hash64_32(q_lo, q_hi, oth_meta[0], jnp) % jnp.uint32(ma)
        ib = hash64_32(q_lo, q_hi, oth_meta[1], jnp) % jnp.uint32(mb)
        bit_a = (words_a[(ia >> jnp.uint32(5)).astype(jnp.int32)]
                 >> (ia & jnp.uint32(31))) & jnp.uint32(1)
        bit_b = (words_b[(ib >> jnp.uint32(5)).astype(jnp.int32)]
                 >> (ib & jnp.uint32(31))) & jnp.uint32(1)
        choice = (bit_a ^ bit_b).astype(jnp.bool_)
        b0, b1 = ludo.candidate_buckets(q_lo, q_hi, nb, jnp)
        bucket = jnp.where(choice, b1, b0).astype(jnp.int32)
        slot = slot_hash(q_lo, q_hi, seeds[bucket], jnp).astype(jnp.int32)
        return bucket, slot

    def mn_touch(slots_lo, slots_hi, h, b_loc, s_idx, my_row):
        """The memory-node work: two dependent gathers, zero compute."""
        h_klo, h_khi, h_vlo, h_vhi = h
        sl = slots_lo[b_loc, s_idx]
        sh_ = slots_hi[b_loc, s_idx]
        addr = slots.unpack_addr32(sl, sh_, jnp).astype(jnp.int32)
        length = slots.unpack_len(sh_, jnp)
        a_loc = jnp.clip(addr - my_row * heap_per_row, 0, heap_per_row - 1)
        k_lo = jnp.where(length == 0, jnp.uint32(SENT), h_klo[a_loc])
        k_hi = jnp.where(length == 0, jnp.uint32(SENT), h_khi[a_loc])
        return k_lo, k_hi, h_vlo[a_loc], h_vhi[a_loc]

    def spmd_get(q_lo, q_hi, *arrays):
        if cache is not None:
            cache_arrays = tuple(a[0] for a in arrays[:5])
            arrays = arrays[5:]
        (words_a, words_b, seeds, oth_meta, slots_lo, slots_hi,
         h_klo, h_khi, h_vlo, h_vhi) = [a[0] for a in arrays]
        B = q_lo.shape[0]

        # -- CN-cache probe: hits never enter the routing bins --------------
        shard = (hash64_32(q_lo, q_hi, _ROUTE_SEED, jnp) % jnp.uint32(M))
        if cache is not None:
            c_hit, c_vlo, c_vhi = cache_probe(q_lo, q_hi, cache_arrays,
                                              cache.nsets, jnp)
            shard = jnp.where(c_hit, jnp.uint32(M), shard)

        # -- phase 0: service-layer routing to shard columns ('model') ------
        route_m = bin_by(shard, M, cap_m)
        s_lo = _a2a(take(q_lo, route_m, SENT).reshape(M, cap_m), "model")
        s_hi = _a2a(take(q_hi, route_m, SENT).reshape(M, cap_m), "model")
        r_lo, r_hi = s_lo.reshape(-1), s_hi.reshape(-1)
        sent = jnp.uint32(SENT)
        r_valid = ~((r_lo == sent) & (r_hi == sent))

        # -- CN compute (this device is a CN of its column's shard) ---------
        bucket, slot = cn_locate(r_lo, r_hi, words_a, words_b, seeds, oth_meta)
        row = jnp.minimum(bucket // nb_per_row, D - 1)
        row = jnp.where(r_valid, row, D - 1).astype(jnp.int32)
        my_row = jax.lax.axis_index("data").astype(jnp.int32)

        if variant == "outback":
            # -- THE one round trip over 'data': send (bucket, slot) --------
            route_d = bin_by(row, D, cap_d)
            req = jnp.stack([
                bucket.astype(jnp.uint32),
                slot.astype(jnp.uint32),
                r_lo, r_hi,  # keys ride along only for lane validity
            ], axis=-1)
            req = _a2a(take(req, route_d, SENT).reshape(D, cap_d, 4), "data")
            req = req.reshape(-1, 4)
            b_loc = jnp.clip(req[:, 0].astype(jnp.int32) - my_row * nb_per_row,
                             0, nb_per_row - 1)
            s_idx = jnp.minimum(req[:, 1].astype(jnp.int32), 3)
            k_lo, k_hi, v_lo, v_hi = mn_touch(
                slots_lo, slots_hi, (h_klo, h_khi, h_vlo, h_vhi),
                b_loc, s_idx, my_row)
            resp = jnp.stack([k_lo, k_hi, v_lo, v_hi], -1)
            resp = _a2a(resp.reshape(D, cap_d, 4), "data").reshape(-1, 4)
            back = unbin(route_d, resp, bucket.shape[0], SENT)
        else:  # -- 'race': two dependent one-sided gather phases ------------
            route_d = bin_by(row, D, cap_d)
            req = take(bucket.astype(jnp.uint32), route_d, SENT)
            req = _a2a(req.reshape(D, cap_d), "data").reshape(-1)
            b_loc = jnp.clip(req.astype(jnp.int32) - my_row * nb_per_row,
                             0, nb_per_row - 1)
            grp = jnp.stack([slots_lo[b_loc], slots_hi[b_loc]], -1)  # (n,4,2)
            grp = _a2a(grp.reshape(D, cap_d, 8), "data").reshape(-1, 4, 2)
            grp = unbin(route_d, grp, bucket.shape[0], 0)
            # CN selects the slot from the fetched group and derives the addr.
            rowsel = jnp.arange(bucket.shape[0])
            sl = grp[rowsel, slot, 0]
            sh_ = grp[rowsel, slot, 1]
            addr = slots.unpack_addr32(sl, sh_, jnp).astype(jnp.int32)
            length = slots.unpack_len(sh_, jnp)
            # phase B: one-sided heap fetch from the row owning the address.
            hrow = jnp.minimum(addr // heap_per_row, D - 1).astype(jnp.int32)
            hrow = jnp.where(r_valid & (length != 0), hrow, D - 1)
            route_h = bin_by(hrow, D, cap_d)
            areq = _a2a(take(addr.astype(jnp.uint32), route_h, 0)
                        .reshape(D, cap_d), "data").reshape(-1)
            a_loc = jnp.clip(areq.astype(jnp.int32) - my_row * heap_per_row,
                             0, heap_per_row - 1)
            blk = jnp.stack([h_klo[a_loc], h_khi[a_loc],
                             h_vlo[a_loc], h_vhi[a_loc]], -1)
            blk = _a2a(blk.reshape(D, cap_d, 4), "data").reshape(-1, 4)
            back = unbin(route_h, blk, bucket.shape[0], SENT)
            dead = (length == 0) | ~r_valid
            back = back.at[:, 0].set(
                jnp.where(dead, jnp.uint32(SENT), back[:, 0]))

        # -- back over 'model' to the origin CN, full-key check -------------
        resp_m = _a2a(back.reshape(M, cap_m, 4), "model").reshape(-1, 4)
        final = unbin(route_m, resp_m, B, SENT)
        match = (final[:, 0] == q_lo) & (final[:, 1] == q_hi)
        if cache is None:
            return final[:, 2], final[:, 3], match
        v_lo = jnp.where(c_hit, c_vlo, final[:, 2])
        v_hi = jnp.where(c_hit, c_vhi, final[:, 3])
        return v_lo, v_hi, match | c_hit, c_hit

    qspec = P(("data", "model"))
    cache_specs = _cache_specs() if cache is not None else ()
    out_specs = ((qspec, qspec, qspec) if cache is None
                 else (qspec, qspec, qspec, qspec))
    fn = shard_map(spmd_get, mesh=mesh,
                       in_specs=(qspec, qspec, *cache_specs,
                                 *st.array_specs()),
                       out_specs=out_specs)
    jitted = jax.jit(fn)
    if st.meter is None:
        return jitted, (cap_m, cap_d)

    # Transport seam: meter each batched Get with the same per-op protocol
    # costs the scalar paths account, so the mesh workload replays on the
    # simulated RDMA clock.  Pure observation — results pass through.
    from repro.core.baselines import RaceKVS  # local: avoids import cycle

    def metered_get(q_lo, q_hi, *arrays):
        out = jitted(q_lo, q_hi, *arrays)
        n = int(np.prod(q_lo.shape))
        if cache is not None:
            n_hit = int(np.asarray(out[3]).sum())
            meter_cache_batch(st.meter, n_hit, 0)
            n -= n_hit
        if variant == "race":
            st.meter.add(n, rts=2, req=32,
                         resp=2 * RaceKVS.GROUP_BYTES + KV_BLOCK_BYTES,
                         one_sided=True, cn_hash=3,
                         cn_cmp=2 * RaceKVS.GROUP_SLOTS + 1)
        else:
            st.meter.add(n, rts=1, req=GET_REQ_BYTES, resp=KV_BLOCK_BYTES,
                         cn_hash=5, cn_cmp=1, mn_reads=2)
        return out

    return metered_get, (cap_m, cap_d)


def place_state(mesh: Mesh, st: ShardedKVSState):
    """device_put the stacked arrays with their pool shardings."""
    return tuple(
        jax.device_put(arr, NamedSharding(mesh, spec))
        for arr, spec in zip(st.arrays(), st.array_specs()))


def _cache_specs():
    # one CN-cache replica per device: leading axis sharded over the whole
    # mesh, so each device's block is its own (nsets, ways) copy
    spec = P(("data", "model"))
    return (spec,) * 5


def place_cache(mesh: Mesh, cache: ShardedCNCache):
    """device_put one CN-cache replica per device (leading ndev axis)."""
    ndev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    if cache.ndev != ndev:
        raise ValueError(f"cache built for {cache.ndev} devices, mesh has {ndev}")
    return tuple(
        jax.device_put(arr, NamedSharding(mesh, spec))
        for arr, spec in zip(cache.arrays(), _cache_specs()))
