"""Public jit'd wrappers around the Pallas kernels.

Backend dispatch: on TPU the Pallas kernels run natively, except the
Ludo locator, which the chip's compiler refuses (see :func:`ludo_lookup`);
everywhere else (CPU CI, the 512-device dry-run) the pure-jnp oracles from
``ref.py`` are used — same signature, same outputs.  ``mode='pallas'`` off
the chip runs the kernel under the Pallas interpreter (the
correctness-validation mode used by the kernel test sweeps).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.fused_norm_matmul import fused_norm_matmul_kernel
from repro.kernels.ludo_lookup import ludo_lookup_kernel
from repro.kernels.paged_attention import (cuckoo_paged_attention_kernel,
                                           paged_attention_kernel)
from repro.kernels.slot_unpack import slot_unpack_kernel


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def ludo_lookup(key_lo, key_hi, words_a, words_b, seeds, meta, *,
                mode: str = "auto", block: int = 1024):
    """Batched CN locator. ``meta`` = dict(ma, mb, nb, seed_a, seed_b,
    seed_ba, seed_bb). mode: 'auto' | 'pallas' | 'ref'.

    ``auto`` runs the jnp locator on every backend, TPU included: the
    chip's compiler refuses the Pallas kernel (its whole-array gathers
    from VMEM raise ``NotImplementedError: Only 2D gather is supported``).
    ``pallas`` on a TPU therefore raises that error; elsewhere it runs the
    kernel under the Pallas interpreter."""
    if mode != "pallas":
        return ref.ludo_lookup_ref(
            key_lo, key_hi, words_a, words_b, seeds,
            ma=meta["ma"], mb=meta["mb"], nb=meta["nb"],
            seed_a=meta["seed_a"], seed_b=meta["seed_b"])
    B = key_lo.shape[0]
    Bp = _round_up(B, block)
    pad = Bp - B
    if pad:
        key_lo = jnp.pad(key_lo, (0, pad))
        key_hi = jnp.pad(key_hi, (0, pad))
    bucket, slot = ludo_lookup_kernel(
        key_lo, key_hi, words_a, words_b, seeds.astype(jnp.int32),
        ma=meta["ma"], mb=meta["mb"], nb=meta["nb"], seed_a=meta["seed_a"],
        seed_b=meta["seed_b"], seed_ba=meta["seed_ba"],
        seed_bb=meta["seed_bb"], block=block, interpret=not on_tpu())
    return bucket[:B], slot[:B]


def slot_unpack(s_lo, s_hi, *, mode: str = "auto", block: int = 2048):
    use_pallas = mode == "pallas" or (mode == "auto" and on_tpu())
    if not use_pallas:
        return ref.slot_unpack_ref(s_lo, s_hi)
    B = s_lo.shape[0]
    Bp = _round_up(B, block)
    if Bp != B:
        s_lo = jnp.pad(s_lo, (0, Bp - B))
        s_hi = jnp.pad(s_hi, (0, Bp - B))
    outs = slot_unpack_kernel(s_lo, s_hi, block=block, interpret=not on_tpu())
    return tuple(o[:B] for o in outs)


def paged_attention(q, k_pool, v_pool, page_map, seq_len, *,
                    mode: str = "auto"):
    """Ludo-paged flash decode for one sequence -> (o, m, l) partials."""
    use_pallas = mode == "pallas" or (mode == "auto" and on_tpu())
    if not use_pallas:
        return ref.paged_attention_ref(q, k_pool, v_pool, page_map,
                                       jnp.asarray(seq_len, jnp.int32))
    lens = jnp.asarray([seq_len], jnp.int32).reshape(1)
    return paged_attention_kernel(q, k_pool, v_pool,
                                  page_map.astype(jnp.int32), lens,
                                  interpret=not on_tpu())


def cuckoo_paged_attention(q, k_pool, v_pool, page_map2, select, seq_len, *,
                           mode: str = "auto"):
    """The probing 2-fetch baseline (RACE analogue at kernel level)."""
    use_pallas = mode == "pallas" or (mode == "auto" and on_tpu())
    if not use_pallas:
        pm = page_map2[jnp.arange(page_map2.shape[0]), select]
        return ref.paged_attention_ref(q, k_pool, v_pool, pm,
                                       jnp.asarray(seq_len, jnp.int32))
    lens = jnp.asarray([seq_len], jnp.int32).reshape(1)
    return cuckoo_paged_attention_kernel(
        q, k_pool, v_pool, page_map2.astype(jnp.int32),
        select.astype(jnp.int32), lens, interpret=not on_tpu())


def fused_norm_matmul(x, gamma, w, *, mode: str = "auto",
                      block_s: int = 256, block_f: int = 512):
    use_pallas = mode == "pallas" or (mode == "auto" and on_tpu())
    if not use_pallas:
        return ref.fused_norm_matmul_ref(x, gamma, w)
    S, F = x.shape[0], w.shape[1]
    Sp, Fp = _round_up(S, block_s), _round_up(F, block_f)
    xp = jnp.pad(x, ((0, Sp - S), (0, 0))) if Sp != S else x
    wp = jnp.pad(w, ((0, 0), (0, Fp - F))) if Fp != F else w
    out = fused_norm_matmul_kernel(xp, gamma, wp, block_s=block_s,
                                   block_f=block_f, interpret=not on_tpu())
    return out[:S, :F]


def cn_meta_from(shard_or_cn) -> dict:
    """Extract the kernel meta dict from an OutbackShard / LudoCN."""
    from repro.core.ludo import SEED_BUCKET_A, SEED_BUCKET_B
    cn = getattr(shard_or_cn, "cn", shard_or_cn)
    oth = cn.othello
    return dict(ma=oth.ma, mb=oth.mb, nb=cn.num_buckets,
                seed_a=oth.seed_a, seed_b=oth.seed_b,
                seed_ba=SEED_BUCKET_A, seed_bb=SEED_BUCKET_B)


def flash_combine(o_parts, m_parts, l_parts):
    return ref.combine_flash_partials(o_parts, m_parts, l_parts)
