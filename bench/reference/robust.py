"""Plain reference of the paper's weighted robust rules and of the server's
mu^2-SGD / AnyTime update (Dahan & Levy, "Weight for Robustness", Alg. 1-2),
in straight ``jax.numpy`` at float32. It imports nothing of the program.

A "tree" here is a dict of arrays whose leading axis is the worker (or group)
axis m; distances for the centered trimmed mean are taken over the whole
concatenated vector, so every leaf contributes to one (m,) distance.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def weighted_median(x, s):
    """omega-CWMed of x: (m, n) with weights s: (m,), per coordinate. Sorted
    ascending with weights carried along, the first value whose cumulative
    weight passes half the total; where a prefix meets half exactly, the mean
    of that value and the next.

    The sort is an odd-even transposition network over the m rows: m rounds
    of compare-and-swap, each elementwise over the columns, stable because
    equal values never swap."""
    m = x.shape[0]
    s = s.astype(F32)
    v = [x[i].astype(F32) for i in range(m)]
    w = [jnp.broadcast_to(s[i], v[i].shape) for i in range(m)]
    for r in range(m):
        for i in range(r % 2, m - 1, 2):
            swap = v[i] > v[i + 1]
            v[i], v[i + 1] = (jnp.where(swap, v[i + 1], v[i]),
                              jnp.where(swap, v[i], v[i + 1]))
            w[i], w[i + 1] = (jnp.where(swap, w[i + 1], w[i]),
                              jnp.where(swap, w[i], w[i + 1]))
    half = 0.5 * jnp.sum(s)
    cw, acc = [], jnp.zeros_like(v[0])
    for i in range(m):
        acc = acc + w[i]
        cw.append(acc)
    med, tie, mid = v[m - 1], jnp.zeros(v[0].shape, bool), v[m - 1]
    for i in reversed(range(m)):         # the first index that qualifies wins
        med = jnp.where(cw[i] > half, v[i], med)
        if i < m - 1:
            hit = cw[i] == half
            tie = tie | hit
            mid = jnp.where(hit, 0.5 * (v[i] + v[i + 1]), mid)
    return jnp.where(tie, mid, med)


def tree_median(tree: dict, s, block: int = 1 << 22) -> dict:
    """weighted_median leaf by leaf, one block of columns after another, so
    that a sort never holds more than m x ``block`` values."""
    def leaf(x):
        m = x.shape[0]
        flat = x.reshape(m, -1)
        n = flat.shape[1]
        b = min(block, n)
        full = (n // b) * b

        def body(i, out):
            xb = jax.lax.dynamic_slice_in_dim(flat, i * b, b, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(
                out, weighted_median(xb, s), i * b, axis=0)

        out = jax.lax.fori_loop(0, n // b, body, jnp.zeros((n,), F32))
        if full < n:
            out = out.at[full:].set(weighted_median(flat[:, full:], s))
        return out.reshape(x.shape[1:])

    return jax.tree_util.tree_map(leaf, tree)


def trimmed_weights(d2, s, lam: float):
    """Alg. 1's kept weight per row: rows in order of distance to the anchor
    keep weight until (1 - lam) of the total is reached; the boundary row
    keeps the part that fits. Returns (kept (m,), kept total)."""
    s = s.astype(F32)
    order = jnp.argsort(d2)
    ws = s[order]
    thresh = (1.0 - lam) * jnp.sum(s)
    prev = jnp.cumsum(ws) - ws
    kept = jnp.zeros_like(s).at[order].set(jnp.clip(thresh - prev, 0.0, ws))
    return kept, thresh


def ctma(tree: dict, s, lam: float, anchor: dict) -> dict:
    """omega-CTMA around ``anchor``: the weighted mean of the (1 - lam)
    weight-mass of rows closest to it, distances over the whole tree. Rows
    are read one at a time, so no float32 copy of the stacked tree is made."""
    m = s.shape[0]

    def sq(x, a):
        a = a.astype(F32)
        return jnp.stack([jnp.sum((x[i].astype(F32) - a) ** 2) for i in range(m)])

    d2 = sum(jax.tree_util.tree_leaves(jax.tree_util.tree_map(sq, tree, anchor)))
    kept, total = trimmed_weights(d2, s, lam)
    return jax.tree_util.tree_map(
        lambda x: sum(kept[i] * x[i].astype(F32) for i in range(m)) / total, tree)


def ctma_cwmed(tree: dict, s, lam: float) -> dict:
    return ctma(tree, s, lam, tree_median(tree, s))


def corrected_momentum(g, g_prev_point, d_prev, beta: float, first: bool):
    """mu^2-SGD's worker estimate: d = g(x_t) + (1 - beta)(d_prev - g(x_{t-1})),
    and d = g(x_1) on a worker's first update."""
    if first:
        return g
    return g + (1.0 - beta) * (d_prev - g_prev_point)


def anytime_update(w, x, d_hat, lr: float, gamma: float, dtype=None):
    """The server's constant-gamma update: w <- w - lr d_hat, then
    x <- x + gamma (w_new - x), leaf by leaf. With ``dtype`` the state is
    stored in it: w_new is rounded to it before x reads it, and x after.
    Returns (w_new, x_new)."""
    tm = jax.tree_util.tree_map
    w_new = tm(lambda a, d: a - lr * d, w, d_hat)
    if dtype is not None:
        w_new = tm(lambda a: a.astype(dtype).astype(F32), w_new)
    x_new = tm(lambda a, b: a + gamma * (b - a), x, w_new)
    if dtype is not None:
        w_new, x_new = (tm(lambda a: a.astype(dtype), t) for t in (w_new, x_new))
    return w_new, x_new
