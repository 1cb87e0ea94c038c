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
    "wcwmed_leaf": lambda x, s, y: wcwmed.wcwmed_leaf(
        x.reshape(M, 1024, -1), s, interpret=False),
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
READER_MATCH = {"wcwmed": "wcwmed", "wcwmed_leaf": "wcwmed",
                "wctma_fused": "wctma_anchor_dist|wcomb",
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


# the train cell's largest group-momentum leaves: the embedding and the
# layer-stacked MLP matrices of qwen2-1.5b-4l, m = 4 groups
CELL_LEAVES = [(4, 151936, 1536), (4, 4, 1536, 8960), (4, 4, 8960, 1536)]


@pytest.mark.parametrize("dims", CELL_LEAVES, ids=str)
def test_leaf_median_compiles_at_cell_shapes(shape, dims):
    _mosaic(lambda x, s: wcwmed.wcwmed_leaf(x, s, interpret=False),
            shape(dims, bf16), shape((dims[0],), f32))


def _instructions(hlo: str) -> dict:
    """name -> (opcode, operand names, output type) of every instruction in
    HLO text."""
    out = {}
    for line in hlo.splitlines():
        head = re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)", line)
        if not head:
            continue
        op = re.search(r"\s([a-z][\w\-]*)\(([^)]*)\)", " " + head.group(2))
        if op:
            out[head.group(1)] = (op.group(1),
                                  re.findall(r"%([\w.\-]+)", op.group(2)),
                                  head.group(2)[:op.start()])
    return out


def _layout(hlo_type: str) -> tuple:
    """(minor-to-major, tiling) of an array type, its memory space left out:
    ``bf16[4,2,8]{2,1,0:T(8,128)(2,1)S(1)}`` -> ((2, 1, 0), "T(8,128)(2,1)")."""
    mm, tiling = re.search(r"\{([\d,]*):?((?:T\([\d,]+\))(?:\([\d,]+\))*)?",
                           hlo_type).groups()
    return tuple(int(d) for d in mm.split(",")), tiling


def test_stacked_ctma_reads_each_leaf_in_place(shape):
    """ω-CTMA over ω-CWMed of a small layer-stacked tree, compiled for the
    chip: every leaf's median is a ``wcwmed_leaf`` call that reads the leaf
    in its own layout. No copy, transpose or pad relayouts it on the way:
    the ops between the parameter and the call (bitcasts, and the moves that
    prefetch a small leaf into VMEM) keep its tiling and its dims' order
    (the flat kernel's (m, d) view of a tiled leaf is a relayout copy)."""
    from repro.agg import resolve

    m, L, d, f, v = 4, 2, 256, 512, 1024
    dims = {"embed": (m, v, d), "final_norm": (m, d),
            "groups": [{"ln1": (m, L, d), "ln2": (m, L, d),
                        "mix": {"wq": (m, L, d, d), "bq": (m, L, d),
                                "wk": (m, L, d, 128), "bk": (m, L, 128)},
                        "mlp": {"wg": (m, L, d, f), "wd": (m, L, f, d)}}]}
    tree = jax.tree_util.tree_map(lambda t: shape(t, bf16), dims,
                                  is_leaf=lambda t: isinstance(t, tuple))
    agg = resolve("ctma:cwmed@pallas", lam=0.25, interpret=False)
    hlo = jax.jit(agg).lower(tree, shape((m,), f32)).compile().as_text()
    ins = _instructions(hlo)
    calls = [n for n, (op, _, _) in ins.items()
             if op == "custom-call" and n.startswith("wcwmed")]
    assert len(calls) == len(jax.tree_util.tree_leaves(tree)), calls
    for name in calls:
        assert name.startswith("wcwmed_leaf"), calls
        operand = src = ins[name][1][0]
        while ins[src][0] != "parameter":
            assert ins[src][0] not in ("copy", "transpose", "pad") or \
                _layout(ins[src][2]) == _layout(ins[ins[src][1][0]][2]), \
                (name, src, ins[src])
            src = ins[src][1][0]
        mm, tiling = _layout(ins[operand][2])
        assert (mm, tiling) == (tuple(sorted(mm, reverse=True)),
                                _layout(ins[src][2])[1]), (name, operand, src)
