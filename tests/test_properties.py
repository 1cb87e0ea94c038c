"""Property tests (hypothesis):
the little-attack deviation bound and staleness vote masses."""
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.agg.logits import staleness_weights
from repro.core.attacks import _little_zmax


def _zmax(honest: float, byz: float) -> float:
    return float(_little_zmax(jnp.asarray(float(honest)),
                              jnp.asarray(float(byz))))


@settings(max_examples=40, deadline=None)
@given(st.integers(4, 80), st.integers(2, 40))
def test_little_zmax_nonneg_at_meaningful_byz_mass(n, b):
    """With at least two units of Byzantine mass the supporting-set quantile
    phi = (n - floor(n/2+1))/(n-b) is >= 1/2, so z_max = Phi^{-1}(phi) >= 0:
    the attack never flips to the WRONG side of the honest mean."""
    b = min(b, n // 2)
    assert _zmax(n - b, b) >= 0.0


@settings(max_examples=40, deadline=None)
@given(st.integers(4, 80), st.integers(1, 40))
def test_little_zmax_monotone_in_byz_mass(n, b):
    """At fixed total mass n the quantile's numerator n - floor(n/2+1) does
    not depend on b while the denominator n - b shrinks — more Byzantine
    mass always licenses a LARGER deviation."""
    b = min(b, n // 2)
    assert _zmax(n - b, b) >= _zmax(n - b + 1, b - 1) - 1e-6


@settings(max_examples=40, deadline=None)
@given(st.lists(st.integers(0, 30), min_size=2, max_size=8),
       st.floats(1e-3, 0.5))
def test_staleness_weights_respect_floor(lags, floor):
    w = np.asarray(staleness_weights(lags, floor=floor))
    assert np.all(w >= floor - 1e-7)
    assert np.all(np.isfinite(w))


@settings(max_examples=40, deadline=None)
@given(st.lists(st.integers(0, 30), min_size=2, max_size=8))
def test_staleness_weights_order_preserving_in_lag(lags):
    """Fresher replicas (smaller lag) never carry LESS vote mass, and equal
    lags carry equal mass."""
    w = np.asarray(staleness_weights(lags))
    lags = np.asarray(lags)
    for i in range(len(lags)):
        for j in range(len(lags)):
            if lags[i] < lags[j]:
                assert w[i] >= w[j]
            if lags[i] == lags[j]:
                assert w[i] == w[j]
