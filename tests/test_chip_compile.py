"""The main path's kernels compile for the chip they are written for.

The TPU's compiler is installed here and compiles for a v5e that is
described, not attached (on-chip-measurement guide, section 2): what it
refuses here it refuses on the chip, at no chip time. One case per
kernel of ``chip_smoke.py``'s paths, at the smoke's real shapes; each
must lower to a Mosaic kernel (``tpu_custom_call``). A compile that
passes is not a chip run and says nothing about results or times.
Not here: ``pallas_ragged_compact`` (the fused string decode), which the
v5e's compiler refuses at lowering (ROADMAP.md A2).

The topology is described inside a fixture, never at import or in a
``skipif``/``parametrize`` argument: only one process may load the TPU's
library, and every xdist worker imports every test file.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import spark_rapids_jni_tpu  # noqa: F401
from spark_rapids_jni_tpu.ops import pallas_kernels as pk
from spark_rapids_jni_tpu.ops import ragged_bytes as rb

N = 1 << 20  # 1 Mi rows: the reference's benchmark axis
PROBE_ROWS = 2_880_404  # TPC-DS SF1 store_sales
BUILD_ROWS = 18_000  # TPC-DS SF1 item


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu from describing a chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip: keep it off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture
def tpu_branches(monkeypatch):
    """jax.default_backend() is "cpu" here, so the wrappers in
    ragged_bytes would take their jnp branch: steer them in the test."""
    monkeypatch.setattr(rb, "_use_pallas", lambda: True)


# -- the cases: each takes S(shape, dtype) -> ShapeDtypeStruct on the
# described chip and returns a jax Lowered --------------------------------


def _outer(num_keys, S):
    return pk._outer_impl.lower(S((N,), jnp.int64), S((N,), jnp.float32), num_keys, False)


def _groupby_onehot(S):
    return pk._groupby_impl.lower(S((N,), jnp.int64), S((N,), jnp.float32), 4096, False)


def _partition_map(S):
    return pk._partition_map_impl.lower(S((N,), jnp.int64), 8, False)


def _expand_planes(S):  # the 212-column decode: 53 u32 planes -> 212 u8 rows
    return jax.jit(rb.expand_u32_planes).lower(S((53, N), jnp.uint32))


def _pack_planes(S):
    return jax.jit(rb.pack_u8_planes).lower(S((212, N), jnp.uint8))


def _rotl_take(S):
    return jax.jit(functools.partial(rb.rotl_take, out_w=128)).lower(
        S((N, 256), jnp.uint8), S((N,), jnp.int32))


def _rotl_take32(S):
    return jax.jit(functools.partial(rb.rotl_take32, out_w=128)).lower(
        S((N, 64), jnp.uint32), S((N,), jnp.int32))


def _var_accumulate(S):
    return jax.jit(functools.partial(rb.var_accumulate, maxvar=256)).lower(
        [S((N, 32), jnp.uint8)] * 4, [S((N,), jnp.int32)] * 4)


def _probe_paged(S):
    """The smoke's op-tier join: a dimension-sized build side (paged
    eagerly, on this process's CPU) probed by a fact-sized key column."""
    build = np.random.default_rng(0).permutation(BUILD_ROWS).astype(np.int64) + 1
    t = pk.build_paged_table(jnp.asarray(build))
    assert t is not None, "an 18,000-key build side fits the page table"
    return pk._probe_impl.lower(
        S((PROBE_ROWS,), jnp.uint64), S((PROBE_ROWS,), jnp.bool_),
        S(t.limbs.shape, t.limbs.dtype), S(t.meta.shape, t.meta.dtype),
        t.num_buckets, t.n_pages, t.nlimb, t.c_max, False)


def _asm_epilogue(S):  # one lax.map block of the string encode's assemble
    t, g_tile = 1 << 16, 256
    tile, vec = S((t, g_tile // 4), jnp.uint32), S((t,), jnp.int32)
    return jax.jit(functools.partial(rb._asm_epilogue, g_tile=g_tile)).lower(
        tile, tile, tile, vec, vec, vec)


CASES = {
    "groupby_outer_1Mi_x_4096": functools.partial(_outer, 4096),
    "groupby_outer_1Mi_x_65536": functools.partial(_outer, 65536),
    "groupby_onehot_1Mi_x_4096": _groupby_onehot,
    "partition_map_1Mi_to_8": _partition_map,
    "expand_u32_planes_53_x_1Mi": _expand_planes,
    "pack_u8_planes_212_x_1Mi": _pack_planes,
    "rotl_take_1Mi_x_256": _rotl_take,
    "rotl_take32_1Mi_x_64": _rotl_take32,
    "var_accumulate_4_x_1Mi_x_32": _var_accumulate,
    "probe_paged_18000_x_2880404": _probe_paged,
    "asm_epilogue_65536_x_256": _asm_epilogue,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_compile_cache, tpu_branches):
    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = CASES[case](S).compile()  # raises what the chip's compiler would raise
    assert "tpu_custom_call" in compiled.as_text(), f"{case}: no Mosaic kernel in the program"
