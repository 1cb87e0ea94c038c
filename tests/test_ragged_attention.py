"""Ragged paged attention: oracle vs dense per-request reference, and the
Pallas kernel (interpret mode) vs the oracle.

Property sweep (hypothesis): arbitrary ``cu_q_lens`` splits of a packed
token batch, with q_len=1 decode rows,
multi-token prefill chunks, EMPTY chunks, partial last pages, inter-row
padding gaps and trailing padding, must all agree with a reference that never
sees the packing at all — each request's pages gathered dense, sliced to its
true kv length, and run through plain causal SDPA one request at a time.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.ops import ragged_paged_decode
from repro.kernels.ref import ragged_paged_decode_ref
from repro.models.config import ModelConfig
from repro.models.layers import _sdpa

H, KV, HD = 4, 2, 8
P, PPS = 4, 5                    # page size / pages per row (max kv 20)


def _case(seed: int, n_rows: int, max_q: int = 5):
    """One ragged batch drawn from ``seed``: packed q + pools + table."""
    rng = np.random.default_rng(seed)
    q_lens = rng.integers(0, max_q + 1, n_rows)
    if q_lens.sum() == 0:
        q_lens[rng.integers(0, n_rows)] = 1
    # context AFTER the chunk; rows with q_len=0 may have kv_len=0 too
    kv_lens = np.array([rng.integers(ql, PPS * P + 1) if ql or rng.integers(2)
                        else 0 for ql in q_lens])
    strides = q_lens + rng.integers(0, 3, n_rows)       # inter-row padding
    cu = np.concatenate([[0], np.cumsum(strides)])
    T = int(cu[-1] + rng.integers(0, 3))                # trailing padding
    T = max(T, 1)

    pages_needed = -(-kv_lens // P)
    n_pages = max(int(pages_needed.sum()), 1)
    perm = rng.permutation(n_pages)
    table = np.full((n_rows, PPS), n_pages, np.int32)   # dump everywhere
    nxt = 0
    for r in range(n_rows):
        for j in range(pages_needed[r]):
            table[r, j] = perm[nxt]
            nxt += 1
    q = rng.standard_normal((T, H, HD)).astype(np.float32)
    k_pool = rng.standard_normal((n_pages + 1, P, KV, HD)).astype(np.float32)
    v_pool = rng.standard_normal((n_pages + 1, P, KV, HD)).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(table), jnp.asarray(cu, jnp.int32),
            jnp.asarray(q_lens, jnp.int32), jnp.asarray(kv_lens, jnp.int32))


def _dense_reference(q, k_pool, v_pool, table, cu, q_lens, kv_lens):
    """Per-request dense gather reference: no packing, no dump-row masking —
    each row's pages are gathered dense, SLICED to the true kv length, and
    attended with a plain causal mask at the row's absolute offset."""
    T = q.shape[0]
    cfg = ModelConfig(n_heads=H, n_kv=KV, head_dim=HD)
    out = np.zeros((T, H, HD), np.float32)
    for r in range(table.shape[0]):
        ql, kvl = int(q_lens[r]), int(kv_lens[r])
        if ql == 0:
            continue
        kd = np.asarray(k_pool)[np.asarray(table[r])].reshape(-1, KV, HD)
        vd = np.asarray(v_pool)[np.asarray(table[r])].reshape(-1, KV, HD)
        kd, vd = kd[:kvl], vd[:kvl]                     # true keys only
        qr = q[int(cu[r]):int(cu[r]) + ql]              # (ql, H, hd)
        iq = np.arange(ql)[:, None] + (kvl - ql)
        mask = jnp.asarray(np.arange(kvl)[None, :] <= iq)
        o = _sdpa(cfg, qr[None], jnp.asarray(kd)[None], jnp.asarray(vd)[None],
                  mask[None, None])
        out[int(cu[r]):int(cu[r]) + ql] = \
            np.asarray(o[0], np.float32).reshape(ql, H, HD)
    return out


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 4))
def test_oracle_matches_dense_per_request_reference(seed, n_rows):
    case = _case(seed, n_rows)
    got = np.asarray(ragged_paged_decode_ref(*case))
    want = _dense_reference(*case)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 3))
def test_pallas_kernel_matches_oracle(seed, n_rows):
    case = _case(seed, n_rows)
    want = np.asarray(ragged_paged_decode_ref(*case))
    got = np.asarray(ragged_paged_decode(*case, use_pallas=True,
                                         interpret=True))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def _fixed_case(q_lens, kv_lens, strides=None, trailing=0, seed=7):
    q_lens = np.asarray(q_lens)
    kv_lens = np.asarray(kv_lens)
    rng = np.random.default_rng(seed)
    strides = q_lens if strides is None else np.asarray(strides)
    cu = np.concatenate([[0], np.cumsum(strides)])
    T = int(cu[-1]) + trailing
    pages_needed = -(-kv_lens // P)
    n_pages = max(int(pages_needed.sum()), 1)
    table = np.full((len(q_lens), PPS), n_pages, np.int32)
    nxt = 0
    for r in range(len(q_lens)):
        for j in range(pages_needed[r]):
            table[r, j] = nxt
            nxt += 1
    q = rng.standard_normal((T, H, HD)).astype(np.float32)
    k_pool = rng.standard_normal((n_pages + 1, P, KV, HD)).astype(np.float32)
    v_pool = rng.standard_normal((n_pages + 1, P, KV, HD)).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(table), jnp.asarray(cu, jnp.int32),
            jnp.asarray(q_lens, jnp.int32), jnp.asarray(kv_lens, jnp.int32))


@pytest.mark.parametrize("q_lens,kv_lens,kw", [
    ((1, 1, 1), (5, 13, 1), {}),                 # all-decode, partial pages
    ((3, 0, 2), (9, 0, 7), {}),                  # empty chunk mid-batch
    ((4,), (4,), {"trailing": 3}),               # fresh prefill + trailing pad
    ((2, 1), (18, 20), {"strides": (4, 3)}),     # strided packing, deep ctx
], ids=["all_decode", "empty_chunk", "trailing_pad", "strided"])
def test_edge_cases_oracle_and_kernel(q_lens, kv_lens, kw):
    case = _fixed_case(q_lens, kv_lens, **kw)
    want = _dense_reference(*case)
    oracle = np.asarray(ragged_paged_decode_ref(*case))
    np.testing.assert_allclose(oracle, want, rtol=1e-5, atol=1e-5)
    kern = np.asarray(ragged_paged_decode(*case, interpret=True))
    np.testing.assert_allclose(kern, want, rtol=1e-5, atol=1e-5)
    # padding tokens (inter-row gaps + trailing) come back exactly zero
    claimed = np.zeros(case[0].shape[0], bool)
    cu, ql = np.asarray(case[4]), np.asarray(case[5])
    for r in range(len(ql)):
        claimed[cu[r]:cu[r] + ql[r]] = True
    assert np.all(oracle[~claimed] == 0.0) and np.all(kern[~claimed] == 0.0)
