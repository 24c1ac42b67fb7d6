"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hashing import split_u64, splitmix64
from repro.core.outback import OutbackShard
from repro.core.store import make_uniform_keys
from repro.kernels import ops, ref
from repro.kernels.fused_norm_matmul import fused_norm_matmul_kernel
from repro.kernels.ludo_lookup import ludo_lookup_kernel
from repro.kernels.paged_attention import (cuckoo_paged_attention_kernel,
                                           paged_attention_kernel)
from repro.kernels.slot_unpack import slot_unpack_kernel


# ------------------------------------------------------------- ludo_lookup
@pytest.fixture(scope="module")
def shard():
    keys = make_uniform_keys(40_000)
    return OutbackShard(keys, splitmix64(keys), load_factor=0.9), keys


@pytest.mark.parametrize("batch,block", [(1024, 256), (4096, 1024), (512, 512)])
def test_ludo_lookup_kernel_vs_ref(shard, batch, block):
    sh, keys = shard
    meta = ops.cn_meta_from(sh)
    lo, hi = split_u64(keys[:batch])
    lo, hi = jnp.asarray(lo), jnp.asarray(hi)
    wa = jnp.asarray(sh.cn.othello.words_a)
    wb = jnp.asarray(sh.cn.othello.words_b)
    seeds = jnp.asarray(sh.cn.seeds)
    b_ref, s_ref = ref.ludo_lookup_ref(lo, hi, wa, wb, seeds, ma=meta["ma"],
                                       mb=meta["mb"], nb=meta["nb"],
                                       seed_a=meta["seed_a"], seed_b=meta["seed_b"])
    b_k, s_k = ludo_lookup_kernel(lo, hi, wa, wb, seeds.astype(jnp.int32),
                                  block=block, interpret=True, **meta)
    np.testing.assert_array_equal(np.asarray(b_k), np.asarray(b_ref))
    np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_ref))
    # and both agree with the authoritative host locator
    bb, ss = sh.cn.locate(*split_u64(keys[:batch]))
    np.testing.assert_array_equal(np.asarray(b_ref), bb.astype(np.int32))
    np.testing.assert_array_equal(np.asarray(s_ref), ss.astype(np.int32))


# ------------------------------------------------------------- slot_unpack
@pytest.mark.parametrize("n", [2048, 8192])
def test_slot_unpack_kernel_vs_ref(n):
    rng = np.random.default_rng(0)
    s_lo = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint32))
    s_hi = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint32))
    outs_k = slot_unpack_kernel(s_lo, s_hi, block=1024, interpret=True)
    outs_r = ref.slot_unpack_ref(s_lo, s_hi)
    for a, b in zip(outs_k, outs_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------- paged attention
def _mk_paged(rng, n_kv, g, d, P, ps, L, seq_len, dtype):
    q = jnp.asarray(rng.standard_normal((n_kv, g, d)), dtype)
    k = jnp.asarray(rng.standard_normal((P, ps, n_kv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((P, ps, n_kv, d)), dtype)
    pm = jnp.asarray(rng.choice(P, L, replace=False), jnp.int32)
    return q, k, v, pm


@pytest.mark.parametrize("n_kv,g,d,ps,L,seq_len,dtype", [
    (2, 4, 64, 16, 4, 64, jnp.float32),
    (2, 4, 64, 16, 4, 49, jnp.float32),   # ragged last page
    (4, 2, 128, 32, 8, 250, jnp.float32),
    (1, 8, 64, 16, 2, 32, jnp.bfloat16),
])
def test_paged_attention_kernel_vs_ref(n_kv, g, d, ps, L, seq_len, dtype):
    rng = np.random.default_rng(1)
    q, k, v, pm = _mk_paged(rng, n_kv, g, d, 3 * L, ps, L, seq_len, dtype)
    o_r, m_r, l_r = ref.paged_attention_ref(q, k, v, pm, jnp.int32(seq_len))
    lens = jnp.asarray([seq_len], jnp.int32)
    o_k, m_k, l_k = paged_attention_kernel(q, k, v, pm, lens, interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(m_k), np.asarray(m_r), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_r), rtol=tol, atol=tol)


def test_cuckoo_paged_attention_matches_ludo():
    """The 2-fetch baseline must produce identical attention — it just moves
    2x the pages. (The perf difference shows up in DMA bytes, not values.)"""
    rng = np.random.default_rng(2)
    n_kv, g, d, ps, L, seq = 2, 4, 64, 16, 4, 60
    q, k, v, pm = _mk_paged(rng, n_kv, g, d, 4 * L, ps, L, seq, jnp.float32)
    # candidates: true page in column `sel`, decoy in the other
    decoy = jnp.asarray(rng.choice(4 * L, L, replace=False), jnp.int32)
    sel = jnp.asarray(rng.integers(0, 2, L), jnp.int32)
    pm2 = jnp.where(sel[:, None] == 0, jnp.stack([pm, decoy], 1),
                    jnp.stack([decoy, pm], 1))
    lens = jnp.asarray([seq], jnp.int32)
    o_l, m_l, l_l = paged_attention_kernel(q, k, v, pm, lens, interpret=True)
    o_c, m_c, l_c = cuckoo_paged_attention_kernel(q, k, v, pm2, sel, lens,
                                                  interpret=True)
    np.testing.assert_allclose(np.asarray(o_c), np.asarray(o_l), rtol=1e-5, atol=1e-5)


def test_flash_combine_partials():
    """Sequence-parallel decode: combining per-range partials == full attention."""
    rng = np.random.default_rng(3)
    n_kv, g, d, ps = 2, 4, 64, 16
    L, seq = 8, 128
    q, k, v, pm = _mk_paged(rng, n_kv, g, d, 3 * L, ps, L, seq, jnp.float32)
    o_full, _, _ = ref.paged_attention_ref(q, k, v, pm, jnp.int32(seq))
    # split the pages into two "devices"
    parts = []
    for sl, off in [(slice(0, 4), 0), (slice(4, 8), 64)]:
        o, m, l = ref.paged_attention_ref(q, k, v, pm[sl], jnp.int32(seq - off if off else 64))
        parts.append((o, m, l))
    # ranges: first device owns tokens [0,64), second [64,128)
    o0, m0, l0 = ref.paged_attention_ref(q, k, v, pm[:4], jnp.int32(64))
    o1, m1, l1 = ref.paged_attention_ref(q, k, v, pm[4:], jnp.int32(64))
    o_c = ref.combine_flash_partials([o0, o1], [m0, m1], [l0, l1])
    np.testing.assert_allclose(np.asarray(o_c), np.asarray(o_full), rtol=1e-5, atol=1e-5)


# -------------------------------------------------------- fused norm matmul
@pytest.mark.parametrize("S,d,F,dtype,bs,bf", [
    (256, 512, 1024, jnp.float32, 128, 256),
    (512, 256, 512, jnp.float32, 256, 512),
    (128, 1024, 512, jnp.bfloat16, 128, 128),
])
def test_fused_norm_matmul_vs_ref(S, d, F, dtype, bs, bf):
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((S, d)), dtype)
    gamma = jnp.asarray(rng.standard_normal((d,)), dtype)
    w = jnp.asarray(rng.standard_normal((d, F)) / np.sqrt(d), dtype)
    out_k = fused_norm_matmul_kernel(x, gamma, w, block_s=bs, block_f=bf,
                                     interpret=True)
    out_r = ref.fused_norm_matmul_ref(x, gamma, w)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32), rtol=tol, atol=tol)


# --------------------------------------------------------------- ops layer
def test_ops_dispatch_ref_on_cpu(shard):
    sh, keys = shard
    meta = ops.cn_meta_from(sh)
    lo, hi = split_u64(keys[:256])
    b, s = ops.ludo_lookup(jnp.asarray(lo), jnp.asarray(hi),
                           jnp.asarray(sh.cn.othello.words_a),
                           jnp.asarray(sh.cn.othello.words_b),
                           jnp.asarray(sh.cn.seeds), meta)
    bb, ss = sh.cn.locate(lo, hi)
    np.testing.assert_array_equal(np.asarray(b), bb.astype(np.int32))
    np.testing.assert_array_equal(np.asarray(s), ss.astype(np.int32))


def test_ops_ludo_lookup_pallas_mode_interprets_off_chip(shard):
    """Off the chip ``mode='pallas'`` runs the kernel under the
    interpreter, padded to its block, and answers as the host locator."""
    sh, keys = shard
    lo, hi = split_u64(keys[:1000])
    b, s = ops.ludo_lookup(jnp.asarray(lo), jnp.asarray(hi),
                           jnp.asarray(sh.cn.othello.words_a),
                           jnp.asarray(sh.cn.othello.words_b),
                           jnp.asarray(sh.cn.seeds), ops.cn_meta_from(sh),
                           mode="pallas", block=512)
    bb, ss = sh.cn.locate(lo, hi)
    np.testing.assert_array_equal(np.asarray(b), bb.astype(np.int32))
    np.testing.assert_array_equal(np.asarray(s), ss.astype(np.int32))
