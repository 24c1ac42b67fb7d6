"""Pallas TPU kernel: batched Ludo locator — the CN-side Get compute.

One grid step processes a block of keys entirely in VMEM/VREGs:
5 murmur-style integer hashes per key (VPU), two packed-bit probes into the
Othello arrays and one seed gather (VMEM dynamic gathers).  The locator
arrays ride whole in VMEM — the decoupling is what makes that possible:
per the paper the CN component costs (2.33 + 2/eps) bits/key, so even a
4M-key shard's locator is ~2.3 MB, comfortably VMEM-resident, while the
memory-heavy half stays in HBM on the "memory pool" devices.

TPU status: the TPU v5e compiler refuses this kernel.  Its whole-array
``jnp.take`` gathers on VMEM refs raise ``NotImplementedError: Only 2D
gather is supported`` at any table size, and its blocks are 1-D.  It has
only ever run under the Pallas interpreter, so ``repro.kernels.ops``
serves the locator with the jnp path on every backend.  Hash math is
uint32 VPU arithmetic — no MXU use, this kernel is bandwidth-trivial and
compute-tiny, exactly like the CN role the paper prescribes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.hashing import _C1, _C2, _C3, _C4, _GOLDEN

DEFAULT_BLOCK = 1024


def _fmix32(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(_C1)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(_C2)
    h = h ^ (h >> 16)
    return h


def _hash64(lo, hi, seed):
    h = jnp.uint32(seed) ^ jnp.uint32(_GOLDEN)
    h = _fmix32(h ^ lo) * jnp.uint32(_C3)
    h = _fmix32(h ^ hi) * jnp.uint32(_C4)
    return _fmix32(h)


def _kernel(klo_ref, khi_ref, wa_ref, wb_ref, seeds_ref, bkt_ref, slot_ref,
            *, ma, mb, nb, seed_a, seed_b, seed_ba, seed_bb):
    lo = klo_ref[...]
    hi = khi_ref[...]
    # Othello probes (bucket locator)
    ia = _hash64(lo, hi, seed_a) % jnp.uint32(ma)
    ib = _hash64(lo, hi, seed_b) % jnp.uint32(mb)
    wa = jnp.take(wa_ref[...], (ia >> jnp.uint32(5)).astype(jnp.int32))
    wb = jnp.take(wb_ref[...], (ib >> jnp.uint32(5)).astype(jnp.int32))
    bit_a = (wa >> (ia & jnp.uint32(31))) & jnp.uint32(1)
    bit_b = (wb >> (ib & jnp.uint32(31))) & jnp.uint32(1)
    choice = (bit_a ^ bit_b).astype(jnp.bool_)
    # candidate cuckoo buckets
    b0 = _hash64(lo, hi, seed_ba) % jnp.uint32(nb)
    b1 = _hash64(lo, hi, seed_bb) % jnp.uint32(nb)
    bucket = jnp.where(choice, b1, b0)
    # seeded in-bucket slot
    seed = jnp.take(seeds_ref[...], bucket.astype(jnp.int32)).astype(jnp.uint32)
    s = _fmix32(lo ^ (seed * jnp.uint32(_C1)) ^ (hi * jnp.uint32(_C2)))
    bkt_ref[...] = bucket.astype(jnp.int32)
    slot_ref[...] = (s & jnp.uint32(3)).astype(jnp.int32)


def ludo_lookup_kernel(key_lo, key_hi, words_a, words_b, seeds, *,
                       ma, mb, nb, seed_a, seed_b, seed_ba, seed_bb,
                       block: int = DEFAULT_BLOCK, interpret: bool = True):
    """B keys -> (bucket, slot); B must be a multiple of ``block``
    (``repro.kernels.ops.ludo_lookup`` pads)."""
    B = key_lo.shape[0]
    assert B % block == 0, (B, block)
    kern = functools.partial(_kernel, ma=ma, mb=mb, nb=nb, seed_a=seed_a,
                             seed_b=seed_b, seed_ba=seed_ba, seed_bb=seed_bb)
    whole = lambda shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    return pl.pallas_call(
        kern,
        grid=(B // block,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            whole(words_a.shape),  # locator arrays: whole, VMEM-resident
            whole(words_b.shape),
            whole(seeds.shape),
        ],
        out_specs=(pl.BlockSpec((block,), lambda i: (i,)),
                   pl.BlockSpec((block,), lambda i: (i,))),
        out_shape=(jax.ShapeDtypeStruct((B,), jnp.int32),
                   jax.ShapeDtypeStruct((B,), jnp.int32)),
        interpret=interpret,
    )(key_lo, key_hi, words_a, words_b, seeds)
