"""Pallas kernel sweeps: shapes × dtypes, assert_allclose vs the pure-jnp
oracles (interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref, wcwmed
from repro.kernels.wctma_fused import wctma_fused
from repro.kernels.wreduce import sqdist_pallas, wcomb_pallas

KEY = jax.random.PRNGKey(0)

SHAPES_MD = [(3, 7), (5, 128), (9, 512), (16, 1000), (32, 2048), (8, 513)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("m,d", SHAPES_MD)
@pytest.mark.parametrize("dtype", DTYPES, ids=str)
def test_wcwmed_sweep(m, d, dtype):
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, m * d))
    x = jax.random.normal(k1, (m, d)).astype(dtype)
    s = jax.random.uniform(k2, (m,), minval=0.1, maxval=3.0)
    np.testing.assert_allclose(np.asarray(ops.wcwmed(x, s)),
                               np.asarray(ref.wcwmed_ref(x, s)),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("m,d", SHAPES_MD[:4])
def test_wcwmed_tie_handling(m, d):
    x = jax.random.normal(jax.random.fold_in(KEY, d), (m, d))
    s = jnp.ones((m,))  # even m hits the exact S/2 prefix tie
    np.testing.assert_allclose(np.asarray(ops.wcwmed(x, s)),
                               np.median(np.asarray(x), axis=0), atol=1e-6)


def test_wcwmed_near_tie_within_one_ulp():
    """A prefix one ulp above S/2 is a tie for the oracle's relative
    tolerance, so the kernel must average it with the next element too."""
    s = np.array([0.5 + 2.0 ** -24, 0.25, 0.25 - 2.0 ** -24], np.float32)
    assert s.sum(dtype=np.float32) == 1.0
    assert np.nextafter(np.float32(0.5), np.float32(1.0)) == s[0]
    x = np.asarray(jax.random.normal(jax.random.fold_in(KEY, 3), (3, 640)))
    xs = np.sort(x, axis=0)                  # row 0 smallest: prefix = s[0]
    for mat in (xs, x):
        want = np.asarray(ref.wcwmed_ref(jnp.asarray(mat), jnp.asarray(s)))
        got = np.asarray(ops.wcwmed(jnp.asarray(mat), jnp.asarray(s)))
        np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(ops.wcwmed(jnp.asarray(xs), jnp.asarray(s))),
        0.5 * (xs[0] + xs[1]), atol=1e-6)


def _leaf_shape(rank, m, dtype, c=256):
    """A leaf (m, *shape) of the given rank whose rows R overrun the leaf
    kernel's row block and are no multiple of it: the last block is partial."""
    if rank == 2:
        return (m, 3 * c)
    br = wcwmed._leaf_block(m, 1 << 30, c, jnp.dtype(dtype).itemsize)[0]
    rows = br + 24
    assert rows % br and rows > br
    return (m, rows, c) if rank == 3 else (m, 2, rows, c)


def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


def _clear_of_tie_rule(x, s):
    """Coordinates whose median the tie rule decides with room to spare: no
    prefix of the sorted weights lies within half the tolerance of the
    tolerance itself from S/2. The kernels sum a prefix in worker order and
    the oracle in sorted order, so where a prefix sits at the tolerance's
    edge their roundings may decide the tie differently."""
    x, s = np.asarray(x, np.float64), np.asarray(s, np.float64)
    m = x.shape[0]
    cum = np.cumsum(s[np.argsort(x, axis=0, kind="stable")], axis=0)
    half = 0.5 * s.sum()
    tol = 4.0 * m * np.finfo(np.float32).eps * half
    edge = np.abs(np.abs(cum - half) - tol) <= 0.5 * tol
    return ~edge.any(axis=0)


@pytest.mark.parametrize("m", [3, 4, 5, 17])
@pytest.mark.parametrize("dtype", DTYPES, ids=str)
@pytest.mark.parametrize("rank", [2, 3, 4])
def test_wcwmed_leaf_matches_flat(rank, dtype, m):
    """The leaf kernel reads (m, C), (m, R, C) and (m, L, R, C) in their own
    layout and equals the flat kernel on the (m, d) view bit for bit."""
    shape = _leaf_shape(rank, m, dtype)
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, 11 * m + rank))
    x = jax.random.normal(k1, shape).astype(dtype)
    s = jax.random.uniform(k2, (m,), minval=0.1, maxval=3.0)
    got = wcwmed.wcwmed_leaf(x, s)
    assert got.shape == shape[1:] and got.dtype == jnp.float32
    flat = x.reshape(m, -1)
    np.testing.assert_array_equal(
        _bits(got).reshape(-1), _bits(wcwmed.wcwmed_pallas(flat, s)))
    clear = _clear_of_tie_rule(flat.astype(jnp.float32), s)
    assert clear.mean() > 0.999
    np.testing.assert_allclose(np.asarray(got).reshape(-1)[clear],
                               np.asarray(ref.wcwmed_ref(flat, s))[clear],
                               atol=1e-5, rtol=1e-5)


def _near_tie(shape):
    """The one-ulp near tie of test_wcwmed_near_tie_within_one_ulp, sorted
    rows so the first prefix is the near tie."""
    s = np.array([0.5 + 2.0 ** -24, 0.25, 0.25 - 2.0 ** -24], np.float32)
    x = np.sort(np.asarray(jax.random.normal(jax.random.fold_in(KEY, 3),
                                             (3,) + shape)), axis=0)
    return jnp.asarray(x), jnp.asarray(s)


def _exact_tie(shape):
    """Equal weights of an even m (prefix sums hit S/2 exactly) over values
    with repeats across workers."""
    x = jax.random.randint(jax.random.fold_in(KEY, 4), (4,) + shape, -3, 4)
    return x.astype(jnp.float32) * 0.5, jnp.ones((4,))


def _zero_weights(shape):
    return jax.random.normal(jax.random.fold_in(KEY, 5), (5,) + shape), \
        jnp.zeros((5,))


def _infinite(shape):
    """A fifth of the values +inf and a fifth -inf, unequal weights: a
    median, or a tie average, of infinities."""
    k1, k2, k3 = jax.random.split(jax.random.fold_in(KEY, 6), 3)
    x = jax.random.normal(k1, (5,) + shape)
    u = jax.random.uniform(k2, x.shape)
    x = jnp.where(u < 0.2, jnp.inf, jnp.where(u > 0.8, -jnp.inf, x))
    return x, jax.random.uniform(k3, (5,), minval=0.1, maxval=3.0)


@pytest.mark.parametrize("shape", [(640,), (2, 40, 384)], ids=str)
@pytest.mark.parametrize("case", [_exact_tie, _near_tie, _zero_weights,
                                  _infinite],
                         ids=lambda f: f.__name__[1:])
def test_wcwmed_leaf_ties(case, shape):
    x, s = case(shape)
    got = wcwmed.wcwmed_leaf(x, s)
    flat = x.reshape(x.shape[0], -1)
    np.testing.assert_array_equal(
        _bits(got).reshape(-1), _bits(wcwmed.wcwmed_pallas(flat, s)))
    np.testing.assert_allclose(np.asarray(got).reshape(-1),
                               np.asarray(ref.wcwmed_ref(flat, s)), atol=1e-6)


def test_wcwmed_leaf_nan_stays_in_its_column():
    """A NaN has no place in the (x, i) order: ``wmed_tile`` counts neither
    of a pair with a NaN before the other, the leaf kernel's one compare a
    pair counts one of them. So in a column that holds a NaN the two may
    pick differently; every other column is the flat kernel's bit for bit."""
    x, s = _infinite((4, 256))
    hit = jax.random.uniform(jax.random.fold_in(KEY, 8), x.shape) < 0.05
    x = jnp.where(hit, jnp.nan, x)
    got = _bits(wcwmed.wcwmed_leaf(x, s)).reshape(-1)
    want = _bits(wcwmed.wcwmed_pallas(x.reshape(5, -1), s))
    clean = ~np.asarray(hit).reshape(5, -1).any(axis=0)
    assert 0.5 < clean.mean() < 1.0
    np.testing.assert_array_equal(got[clean], want[clean])


@pytest.mark.parametrize("m,d", SHAPES_MD)
def test_sqdist_and_wcomb(m, d):
    k1, k2, k3 = jax.random.split(jax.random.fold_in(KEY, 7 * m + d), 3)
    x = jax.random.normal(k1, (m, d))
    y = jax.random.normal(k2, (d,))
    c = jax.random.uniform(k3, (m,), minval=0.0, maxval=2.0)
    np.testing.assert_allclose(np.asarray(sqdist_pallas(x, y)),
                               np.asarray(ref.sqdist_ref(x, y)), rtol=2e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(wcomb_pallas(x, c, 3.7)),
                               np.asarray(ref.wcomb_ref(x, c, 3.7)), rtol=2e-5, atol=1e-5)


@pytest.mark.parametrize("m,d", SHAPES_MD[:4])
def test_wgm_kernel_matches_oracle(m, d):
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, m + d))
    x = jax.random.normal(k1, (m, d))
    s = jax.random.uniform(k2, (m,), minval=0.1, maxval=3.0)
    np.testing.assert_allclose(np.asarray(ops.wgm(x, s, iters=8)),
                               np.asarray(ref.wgm_ref(x, s, iters=8)),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("m,d", SHAPES_MD[:4])
@pytest.mark.parametrize("lam", [0.1, 0.3])
def test_wctma_kernel_matches_oracle(m, d, lam):
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, 3 * m + d))
    x = jax.random.normal(k1, (m, d))
    s = jax.random.uniform(k2, (m,), minval=0.1, maxval=3.0)
    np.testing.assert_allclose(np.asarray(ops.wctma(x, s, lam=lam)),
                               np.asarray(ref.wctma_ref(x, s, lam)),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# fused ω-CTMA (single-pass anchor + distances, then one trimmed combine)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,d", SHAPES_MD)
@pytest.mark.parametrize("dtype", DTYPES, ids=str)
@pytest.mark.parametrize("lam", [0.1, 0.3])
def test_wctma_fused_sweep(m, d, dtype, lam):
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, 11 * m + d))
    x = jax.random.normal(k1, (m, d)).astype(dtype)
    s = jax.random.uniform(k2, (m,), minval=0.1, maxval=3.0)
    np.testing.assert_allclose(np.asarray(wctma_fused(x, s, lam=lam)),
                               np.asarray(ref.wctma_ref(x, s, lam)),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("m,d", SHAPES_MD[:4])
def test_wctma_fused_exact_tie_anchor(m, d):
    """Even m + unit weights hits the exact S/2 prefix tie in the fused
    anchor pass (paper's average-the-adjacent-pair rule)."""
    me = m + (m % 2)  # force even worker count
    x = jax.random.normal(jax.random.fold_in(KEY, 13 * d), (me, d))
    s = jnp.ones((me,))
    np.testing.assert_allclose(np.asarray(wctma_fused(x, s, lam=0.25)),
                               np.asarray(ref.wctma_ref(x, s, 0.25)),
                               atol=1e-5, rtol=1e-5)


def test_wctma_fused_boundary_row_clipping():
    """(1-λ)·Σs falls strictly inside a row's weight interval: the boundary
    row must be kept with exactly the clipped partial mass."""
    x = jnp.stack([jnp.zeros(64), jnp.ones(64), 2.0 * jnp.ones(64),
                   100.0 * jnp.ones(64)])
    s = jnp.asarray([1.0, 1.0, 1.0, 1.0])
    lam = 0.3  # thresh = 2.8 -> kept (sorted by dist) = [1, 1, 0.8, 0]
    got = wctma_fused(x, s, lam=lam)
    want = ref.wctma_ref(x, s, lam)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # the far outlier must be fully trimmed, not merely down-weighted
    assert float(jnp.max(got)) < 2.0


def test_wctma_fused_matches_unfused():
    x = jax.random.normal(jax.random.fold_in(KEY, 77), (9, 777))
    s = jax.random.uniform(jax.random.fold_in(KEY, 78), (9,), minval=0.1, maxval=3.0)
    np.testing.assert_allclose(
        np.asarray(ops.wctma(x, s, lam=0.2, fused=True)),
        np.asarray(ops.wctma(x, s, lam=0.2, fused=False)), atol=1e-5, rtol=1e-5)


def test_wgm_trace_size_independent_of_iters():
    """The fori_loop rewrite must trace the fused Weiszfeld step ONCE: launch
    count and trace size may not grow with iters (previously 1 + 2·iters
    pallas_call launches were unrolled into every trace)."""
    x = jax.random.normal(KEY, (9, 512))
    s = jnp.ones((9,))
    j2 = jax.make_jaxpr(lambda x, s: ops.wgm(x, s, iters=2))(x, s)
    j16 = jax.make_jaxpr(lambda x, s: ops.wgm(x, s, iters=16))(x, s)
    n2, n16 = str(j2).count("pallas_call"), str(j16).count("pallas_call")
    assert n2 == n16 == 2, (n2, n16)  # anchor pass + ONE fused loop body
    assert len(j2.eqns) == len(j16.eqns)


def test_kernel_aggregator_registry_matches_jnp():
    from repro.agg import resolve
    x = jax.random.normal(jax.random.fold_in(KEY, 5), (8, 300))
    s = jax.random.uniform(jax.random.fold_in(KEY, 6), (8,), minval=0.2, maxval=2.0)
    for spec in ("mean", "cwmed", "gm", "ctma:cwmed", "ctma:gm"):
        got = resolve(spec, lam=0.25, backend="pallas")(x, s)
        want = resolve(spec, lam=0.25, backend="jnp")(x, s)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4, err_msg=spec)


SWA_CASES = [
    # B, H, KV, hd, W, local, pos
    (2, 8, 2, 64, 512, True, 100),
    (2, 8, 2, 64, 512, True, 5000),   # wrapped ring
    (1, 4, 4, 128, 256, False, 255),
    (2, 16, 1, 64, 1024, True, 37),
    (1, 2, 2, 32, 256, False, 0),     # first token
]


@pytest.mark.parametrize("B,H,KV,hd,W,local,pos", SWA_CASES)
@pytest.mark.parametrize("dtype", DTYPES, ids=str)
def test_swa_decode_sweep(B, H, KV, hd, W, local, pos, dtype):
    k1, k2, k3 = jax.random.split(jax.random.fold_in(KEY, B * H * W + pos), 3)
    q = jax.random.normal(k1, (B, H, hd)).astype(dtype)
    kc = jax.random.normal(k2, (B, W, KV, hd)).astype(dtype)
    vc = jax.random.normal(k3, (B, W, KV, hd)).astype(dtype)
    p = jnp.asarray(pos, jnp.int32)
    got = ops.swa_decode(q, kc, vc, p, local=local)
    want = ref.swa_decode_ref(q, kc, vc, p, local=local)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol, rtol=tol)


@pytest.mark.parametrize("B,H,KV,hd,W,local", [
    (3, 8, 2, 64, 512, True),
    (3, 8, 2, 64, 512, False),
    (4, 4, 4, 32, 256, True),
    (2, 16, 1, 64, 128, False),
])
def test_swa_decode_per_slot_pos_sweep(B, H, KV, hd, W, local):
    """Vector (B,) pos — the slot-mapped serving form. Rows at depth 0, a
    partially-filled cache, exactly W-1, and a wrapped ring must all match
    the masked-SDPA oracle row-for-row (this used to fall back to SDPA)."""
    k1, k2, k3, k4 = jax.random.split(jax.random.fold_in(KEY, B * W), 4)
    q = jax.random.normal(k1, (B, H, hd))
    kc = jax.random.normal(k2, (B, W, KV, hd))
    vc = jax.random.normal(k3, (B, W, KV, hd))
    pos = jax.random.randint(k4, (B,), 0, 3 * W).astype(jnp.int32)
    pos = pos.at[0].set(0).at[1].set(W - 1)          # edge depths
    got = ops.swa_decode(q, kc, vc, pos, local=local)
    want = ref.swa_decode_ref(q, kc, vc, pos, local=local)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)
    # each row must equal its own scalar-pos decode (per-slot independence)
    for b in range(B):
        solo = ref.swa_decode_ref(q[b:b + 1], kc[b:b + 1], vc[b:b + 1],
                                  pos[b], local=local)
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(solo[0]),
                                   atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# paged flash decode (block-table page pools — serve/cache.py layout)
# ---------------------------------------------------------------------------

PAGED_CASES = [
    # S, H, KV, hd, P, pages_per_slot
    (3, 8, 2, 64, 16, 4),
    (4, 4, 4, 32, 8, 6),
    (2, 16, 1, 64, 32, 2),
    (1, 2, 2, 32, 4, 7),
]


def _paged_fixture(S, H, KV, hd, P, pps, seed):
    """Random pools + a permuted block table + per-slot pos exercising
    depth 0, a partially-filled last page, and the full span."""
    n_pages = S * pps
    ks = jax.random.split(jax.random.fold_in(KEY, seed), 4)
    q = jax.random.normal(ks[0], (S, H, hd))
    kp = jax.random.normal(ks[1], (n_pages + 1, P, KV, hd))
    vp = jax.random.normal(ks[2], (n_pages + 1, P, KV, hd))
    perm = np.random.default_rng(seed).permutation(n_pages)
    tbl = jnp.asarray(perm.reshape(S, pps), jnp.int32)
    pos = jax.random.randint(ks[3], (S,), 0, pps * P).astype(jnp.int32)
    pos = pos.at[0].set(0)                       # first token
    if S > 1:
        pos = pos.at[1].set(pps * P - 1)         # full span
    if S > 2:
        pos = pos.at[2].set(P + P // 2)          # partially-filled last page
    return q, kp, vp, tbl, pos


@pytest.mark.parametrize("S,H,KV,hd,P,pps", PAGED_CASES)
def test_paged_decode_sweep(S, H, KV, hd, P, pps):
    q, kp, vp, tbl, pos = _paged_fixture(S, H, KV, hd, P, pps, seed=S * P)
    got = ops.paged_decode(q, kp, vp, tbl, pos)
    want = ref.paged_decode_ref(q, kp, vp, tbl, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_paged_decode_matches_dense_gather():
    """Paging is a pure relayout: gathering each slot's pages into a dense
    cache and running the dense causal kernel must give the same output."""
    S, H, KV, hd, P, pps = 3, 4, 2, 32, 8, 4
    q, kp, vp, tbl, pos = _paged_fixture(S, H, KV, hd, P, pps, seed=99)
    got = ops.paged_decode(q, kp, vp, tbl, pos)
    kc = kp[tbl].reshape(S, pps * P, KV, hd)
    vc = vp[tbl].reshape(S, pps * P, KV, hd)
    want = ref.swa_decode_ref(q, kc, vc, pos, local=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_paged_decode_dump_pages_masked():
    """Logical pages past ``pos`` may point at the dump page (unallocated):
    whatever garbage lives there must not change the output."""
    S, H, KV, hd, P, pps = 2, 4, 2, 32, 8, 4
    q, kp, vp, tbl, pos = _paged_fixture(S, H, KV, hd, P, pps, seed=7)
    pos = jnp.asarray([P - 2, 2 * P + 1], jnp.int32)   # 1 / 3 pages allocated
    dump = kp.shape[0] - 1
    tbl_dumped = tbl.at[0, 1:].set(dump).at[1, 3:].set(dump)
    a = ops.paged_decode(q, kp, vp, tbl, pos)
    b = ops.paged_decode(q, kp, vp, tbl_dumped, pos)
    # poison the dump page: still identical
    kp2 = kp.at[dump].set(1e4)
    vp2 = vp.at[dump].set(-1e4)
    c = ops.paged_decode(q, kp2, vp2, tbl_dumped, pos)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-6)


@settings(max_examples=15, deadline=None)
@given(st.integers(3, 10), st.integers(1, 50), st.integers(0, 10_000))
def test_wcwmed_property_random(m, d, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (m, d))
    s = jax.random.uniform(k2, (m,), minval=0.05, maxval=5.0)
    np.testing.assert_allclose(np.asarray(ops.wcwmed(x, s)),
                               np.asarray(ref.wcwmed_ref(x, s)), atol=1e-5)


SSD_CASES = [(2, 32, 4, 8, 16, 8), (1, 64, 8, 16, 32, 16), (2, 128, 2, 4, 8, 32)]


@pytest.mark.parametrize("b,s,h,p,n,c", SSD_CASES)
def test_ssd_kernel_matches_oracle(b, s, h, p, n, c):
    ks = jax.random.split(jax.random.fold_in(KEY, s * h + b), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    y1, st1 = ops.ssd_scan(x, dt, A, B, C, c)
    y0, st0 = ref.ssd_ref(x, dt, A, B, C, c)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(st1), np.asarray(st0), atol=1e-3, rtol=1e-3)
