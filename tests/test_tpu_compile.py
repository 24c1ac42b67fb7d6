"""Compile-only guards for the chip: the main path at full size on TPU v5e.

Each test compiles for a described ``v5e:2x2`` topology, which needs the
TPU compiler but no chip, so what the chip's compiler refuses fails here.
The topology is described inside a module-scoped fixture, never at
import, and every test that needs it skips together when it cannot be
described.  Nothing runs, so these tests say nothing about results or
times.  Shapes follow the builders' geometry formulas; the first two
tests hold those formulas to the builders at a small size.
"""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.hashing import hash64_32, split_u64, splitmix64
from repro.core.othello import Othello
from repro.core.outback import OutbackShard, outback_get_batch
from repro.core.sharded_kvs import (_ROUTE_SEED, ShardedKVSState, _round_up,
                                    build_sharded, make_get_fn)
from repro.core.store import make_uniform_keys
from repro.kernels.ludo_lookup import ludo_lookup_kernel
from repro.kernels.slot_unpack import slot_unpack_kernel

PAIRS = 1 << 25  # the chip smoke's default size
LOAD_FACTOR = 0.85
GET_BATCH = 65_536
HBM_BYTES = 16e9  # one TPU v5e


def outback_shapes(n: int, load_factor: float = LOAD_FACTOR):
    """(cn, mn) array shapes/dtypes of an ``OutbackShard`` over n pairs
    (``ludo.build``, ``othello.build``, ``OutbackShard.__init__``)."""
    nb = max(1, math.ceil(n / (4.0 * load_factor)))
    ma = max(4, math.ceil(1.33 * n))
    mb = max(4, n + 1)
    heap = max(16, math.ceil(n * 1.30) + 64)
    cn = (((ma + 31) // 32,), np.uint32), (((mb + 31) // 32,), np.uint32), \
        ((nb,), np.uint8)
    mn = ((nb, 4), np.uint32), ((nb, 4), np.uint32), *[((heap,), np.uint32)] * 4
    return cn, mn, dict(nb=nb, ma=ma, mb=mb)


def sharded_state(n_max: int, num_shards: int, data_parallel: int,
                  load_factor: float = LOAD_FACTOR,
                  heap_slack: float = 1.5) -> ShardedKVSState:
    """A ``ShardedKVSState`` of shapes only, as ``build_sharded`` sizes it
    for a largest shard of ``n_max`` pairs."""
    M, D = num_shards, data_parallel
    nb = _round_up(max(D, math.ceil(n_max / (4.0 * load_factor))), D)
    cap = _round_up(math.ceil(n_max * heap_slack) + 4 * D, D)
    ma, mb = math.ceil(1.33 * n_max) + 7, n_max + 11
    S = jax.ShapeDtypeStruct
    return ShardedKVSState(
        words_a=S((M, (ma + 31) // 32), jnp.uint32),
        words_b=S((M, (mb + 31) // 32), jnp.uint32),
        seeds=S((M, nb), jnp.uint8), oth_meta=S((M, 4), jnp.uint32),
        slots_lo=S((M, nb, 4), jnp.uint32), slots_hi=S((M, nb, 4), jnp.uint32),
        heap_klo=S((M, cap), jnp.uint32), heap_khi=S((M, cap), jnp.uint32),
        heap_vlo=S((M, cap), jnp.uint32), heap_vhi=S((M, cap), jnp.uint32),
        num_buckets=nb, heap_cap=cap, ma=ma, mb=mb)


# ---------------------------------------------- the shapes match the builders
def test_outback_shapes_match_the_builder():
    keys = make_uniform_keys(5_000, 3)
    sh = OutbackShard(keys, splitmix64(keys), load_factor=LOAD_FACTOR)
    cn, mn, geo = outback_shapes(5_000)
    got = sh.cn_arrays() + sh.mn_arrays()
    assert [(a.shape, a.dtype) for a in got] == [
        (shape, np.dtype(dt)) for shape, dt in cn + mn]
    oth = sh.cn.othello
    assert (geo["nb"], geo["ma"], geo["mb"]) == (sh.cn.num_buckets, oth.ma,
                                                  oth.mb)


def test_sharded_state_shapes_match_the_builder():
    keys = make_uniform_keys(6_000, 4)
    st = build_sharded(keys, splitmix64(keys), num_shards=4, data_parallel=2,
                       load_factor=LOAD_FACTOR)
    shard = hash64_32(*split_u64(keys), _ROUTE_SEED) % np.uint32(4)
    n_max = int(np.bincount(shard, minlength=4).max())
    ref = sharded_state(n_max, 4, 2)
    assert [(a.shape, a.dtype) for a in st.arrays()] == [
        (a.shape, a.dtype) for a in ref.arrays()]
    assert (st.num_buckets, st.heap_cap, st.ma, st.mb) == (
        ref.num_buckets, ref.heap_cap, ref.ma, ref.mb)


# ------------------------------------------------------ the described chip
@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def test_jitted_get_compiles_at_full_size(one_chip):
    cn, mn, geo = outback_shapes(PAIRS)
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    oth = Othello(None, None, geo["ma"], geo["mb"], 0x0511AD01, 0x0B5EED02)

    def get(lo, hi, cn, mn):
        return outback_get_batch(lo, hi, cn, mn, oth, geo["nb"], jnp)

    q = S((GET_BATCH,), jnp.uint32)
    compiled = jax.jit(get).lower(
        q, q, tuple(S(*a) for a in cn), tuple(S(*a) for a in mn)).compile()
    mem = compiled.memory_analysis()
    assert 0.8e9 < mem.argument_size_in_bytes < HBM_BYTES
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES


def test_slot_unpack_kernel_compiles(one_chip):
    S = jax.ShapeDtypeStruct((GET_BATCH,), jnp.uint32, sharding=one_chip)
    fn = jax.jit(lambda lo, hi: slot_unpack_kernel(lo, hi, interpret=False))
    assert "tpu_custom_call" in fn.lower(S, S).compile().as_text()


def test_ludo_lookup_kernel_is_refused_by_the_chip_compiler(one_chip):
    """The Pallas locator does not lower for the chip, which is why
    ``ops.ludo_lookup`` serves the jnp locator on TPU.  If this starts to
    compile, route ``mode='auto'`` back to the kernel."""
    n = 40_000
    _, _, geo = outback_shapes(n)
    S = lambda n, dt: jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)
    fn = jax.jit(lambda lo, hi, wa, wb, s: ludo_lookup_kernel(
        lo, hi, wa, wb, s, **geo, seed_a=1, seed_b=2, seed_ba=3, seed_bb=4,
        interpret=False))
    with pytest.raises(NotImplementedError, match="Only 2D gather"):
        fn.lower(S(4096, jnp.uint32), S(4096, jnp.uint32),
                 S((geo["ma"] + 31) // 32, jnp.uint32),
                 S((geo["mb"] + 31) // 32, jnp.uint32),
                 S(geo["nb"], jnp.int32)).compile()


def test_mesh_get_compiles_on_four_chips(topo):
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    # largest of four shards over 2^25 uniform keys: mean + 8 std devs
    n_max = PAIRS // 4 + 8 * math.isqrt(PAIRS // 4)
    st = sharded_state(n_max, num_shards=4, data_parallel=1)
    fn, _ = make_get_fn(mesh, st, GET_BATCH // 4)
    arrays = [jax.ShapeDtypeStruct(a.shape, a.dtype,
                                   sharding=NamedSharding(mesh, spec))
              for a, spec in zip(st.arrays(), st.array_specs())]
    q = jax.ShapeDtypeStruct((GET_BATCH,), jnp.uint32,
                             sharding=NamedSharding(mesh, P(("data", "model"))))
    compiled = fn.lower(q, q, *arrays).compile()
    # at least the two trips over 'model': route to the shard, and back
    a2a = re.findall(r"= \S+ all-to-all(?:-start)?\(", compiled.as_text())
    assert len(a2a) >= 2
    assert compiled.memory_analysis().argument_size_in_bytes < HBM_BYTES
