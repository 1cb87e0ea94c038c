"""Every main-path Pallas kernel compiles for a TPU v5e at real widths.

Mosaic, not the interpreter: each case lowers with ``interpret=False``
against a described (not attached) ``v5e:2x2`` topology and must produce a
``tpu_custom_call``. This catches what interpret mode never checks — block
shapes that do not tile (8, 128), ops Mosaic cannot lower, VMEM overruns.
Each aggregation kernel's custom call must also keep the name the
benchmark's trace readers match, under any enclosing scope.

The topology is described only inside the module fixture: describing it
loads libtpu, which one process at a time may hold, so it must not happen
while any module is imported. The persistent compilation cache is off here:
an entry written for a described chip cannot be read back without one.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops, swa, wcwmed, wctma_fused, wreduce

# qwen2-1.5b attention widths, the serve engine's paged geometry
H, KV, HD, PAGE = 12, 2, 128, 16
SLOTS, CHUNK_ROWS, CHUNK = 8, 1, 16
MAX_LEN = 544
PPS = -(-MAX_LEN // PAGE)
N_PAGES = SLOTS * PPS
# the aggregation server step: m workers, d coordinates
M, D = 17, 2 ** 20

f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one_chip)


def _mosaic(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def _pools(shape):
    pool = shape((N_PAGES + 1, PAGE, KV, HD), bf16)
    return pool, pool


def test_ragged_paged_attention_compiles(shape):
    rows = SLOTS + CHUNK_ROWS
    k, v = _pools(shape)
    _mosaic(lambda *a: swa.ragged_paged_decode_pallas(*a, interpret=False),
            shape((rows * CHUNK, H, HD), bf16), k, v,
            shape((rows, PPS), i32), shape((rows + 1,), i32),
            shape((rows,), i32), shape((rows,), i32))


def test_paged_decode_compiles(shape):
    k, v = _pools(shape)
    _mosaic(lambda *a: swa.paged_decode_pallas(*a, interpret=False),
            shape((SLOTS, H, HD), bf16), k, v, shape((SLOTS, PPS), i32),
            shape((SLOTS,), i32))


def test_dense_decode_compiles(shape):
    cache = shape((SLOTS, 1024, KV, HD), bf16)
    _mosaic(lambda *a: swa.swa_decode_pallas(*a, local=True, block_w=128,
                                             interpret=False),
            shape((SLOTS, H, HD), bf16), cache, cache, shape((SLOTS,), i32))


AGG_KERNELS = {
    "wcwmed": lambda x, s, y: wcwmed.wcwmed_pallas(x, s, interpret=False),
    "wctma_fused": lambda x, s, y: wctma_fused.wctma_fused(
        x, s, lam=0.25, interpret=False),
    "gm_step": lambda x, s, y: wreduce.gm_step_padded(
        x, s, y, ops.FUSED_BLOCK_D, interpret=False),
    "sqdist": lambda x, s, y: wreduce.sqdist_pallas(x, y, interpret=False),
    "wcomb": lambda x, s, y: wreduce.wcomb_pallas(x, s, 3.0,
                                                  interpret=False),
}


@pytest.mark.parametrize("name", sorted(AGG_KERNELS))
def test_aggregation_kernel_compiles(shape, name):
    _mosaic(AGG_KERNELS[name], shape((M, D), f32), shape((M,), f32),
            shape((D,), f32))


# what the benchmark's trace readers match in a kernel's op name: the
# server's aggregation kernels (bench/metrics/agg_kernels_roofline.server.py)
# and, for the median, the train step's (cwmed_kernel_roofline.train.py)
SERVER_MATCH = r"wcwmed|wctma|anchor_dist|wcomb|sqdist|gm_step"
READER_MATCH = {"wcwmed": "wcwmed", "wctma_fused": "wctma_anchor_dist|wcomb",
                "gm_step": "gm_step", "sqdist": "sqdist", "wcomb": "wcomb"}


@pytest.mark.parametrize("name", sorted(AGG_KERNELS))
def test_aggregation_kernel_custom_call_keeps_its_name(shape, name):
    """Traced inside a phase scope, each kernel's custom call is still named
    with the substring its reader matches: the name comes from the kernel's
    ``pallas_call(name=...)``, not from the scope or jit around it."""
    from repro.obs.scopes import AGGREGATE, phase

    def scoped(*a):
        with phase(AGGREGATE):
            return AGG_KERNELS[name](*a)

    hlo = jax.jit(scoped).lower(shape((M, D), f32), shape((M,), f32),
                                shape((D,), f32)).compile().as_text()
    calls = [l.split(" = ")[0].split()[-1].lstrip("%")
             for l in hlo.splitlines() if 'custom_call_target="tpu_custom_call"' in l]
    assert calls and all(re.match(READER_MATCH[name], c) for c in calls), calls
    assert all(re.search(SERVER_MATCH, c) for c in calls), calls
