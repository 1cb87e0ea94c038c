"""Shared small utilities: pytree flattening, PRNG helpers, logging, and the
post-SPMD HLO collective-bytes parser (import-side-effect free — unlike
``repro.launch.dryrun``, which forces a placeholder device platform via
XLA_FLAGS at import time and must never be imported just for the parser)."""
from __future__ import annotations

import logging
import os
import re
import time
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any

logger = logging.getLogger("repro")
if not logger.handlers:
    _h = logging.StreamHandler()
    _h.setFormatter(logging.Formatter("[%(asctime)s %(name)s] %(message)s", "%H:%M:%S"))
    logger.addHandler(_h)
    logger.setLevel(logging.INFO)


# <repo>/.jax_cache: a fixed path (the cache key includes it), listed in
# .gitignore
_REPO_CACHE = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".jax_cache")


def compile_cache_dir() -> str:
    """Where JAX's persistent compilation cache lives: the directory that
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``<repo>/.jax_cache``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _REPO_CACHE


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; every entry point (the
    launchers, ``benchmarks/run.py``, ``chip_smoke.py``) calls this once,
    importing a module never does. With ``JAX_COMPILATION_CACHE_DIR`` set,
    JAX already reads it and nothing else is set here."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def ravel_pytree_fn(tree: Pytree) -> tuple[jnp.ndarray, Callable[[jnp.ndarray], Pytree]]:
    """Like jax.flatten_util.ravel_pytree but returns (flat, unravel)."""
    from jax.flatten_util import ravel_pytree

    flat, unravel = ravel_pytree(tree)
    return flat, unravel


def tree_size(tree: Pytree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def tree_bytes(tree: Pytree) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))


def tree_zeros_like(tree: Pytree) -> Pytree:
    return jax.tree_util.tree_map(jnp.zeros_like, tree)


def tree_add(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree_util.tree_map(jnp.add, a, b)


def tree_scale(a: Pytree, s) -> Pytree:
    return jax.tree_util.tree_map(lambda x: x * s, a)


def tree_axpy(alpha, x: Pytree, y: Pytree) -> Pytree:
    """alpha*x + y."""
    return jax.tree_util.tree_map(lambda u, v: alpha * u + v, x, y)


def tree_sqnorm(tree: Pytree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)


def global_norm(tree: Pytree) -> jnp.ndarray:
    return jnp.sqrt(tree_sqnorm(tree))


def split_key(key, n: int):
    return jax.random.split(key, n)


class Timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.dt = time.perf_counter() - self.t0


def timeit_median(fn: Callable[[], Any], iters: int = 5, warmup: int = 2) -> float:
    """Median wall-clock seconds of fn(); blocks on jax arrays."""
    for _ in range(warmup):
        out = fn()
        jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


# ---------------------------------------------------------------------------
# Post-SPMD HLO collective accounting (used by launch/dryrun.py, the agghier
# bench, and the hierarchy HLO tests)
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(
    r"(f64|f32|bf16|f16|s64|u64|s32|u32|s16|u16|s8|u8|pred|f8e4m3fn|f8e5m2)"
    r"\[([0-9,]*)\]")

# ``(-start)?(?![\w-])`` keeps async HLO pairs from double-counting: the
# ``-start`` op matches once (only its RESULT tuple element is counted — the
# tuple also repeats the operand shape), the ``-done`` op is rejected —
# otherwise "all-reduce-done" would count as a second all-reduce (and
# "all-gather-done" as a spurious all-gather).
_COLL_RE = re.compile(
    r"(?:ROOT )?%?[\w.\-]+ = (.*?) (all-gather|all-reduce|reduce-scatter|"
    r"all-to-all|collective-permute)(-start)?(?![\w.\-])")


def _one_shape_bytes(dt: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def _shape_bytes(text: str) -> int:
    return sum(_one_shape_bytes(dt, dims)
               for dt, dims in _SHAPE_RE.findall(text))


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective-kind bytes (per device), parsed from post-SPMD HLO.

    Bytes are the result-shape sizes (all-reduce counted twice for the
    ring's reduce-scatter + all-gather phases)."""
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _COLL_RE.match(line.strip())
        if not m:
            continue
        result_txt, kind, start = m.groups()
        if start:
            # async: the -start tuple is (operands..., results...) — count
            # only the results half (variadic combined collectives carry N
            # of each; the whole tuple would report 2x the bytes of the same
            # collective lowered synchronously). Dimensionless u32[] context
            # scalars some -start tuples append are dropped first.
            shapes = [sh for sh in _SHAPE_RE.findall(result_txt) if sh[1]]
            b = sum(_one_shape_bytes(*sh) for sh in shapes[len(shapes) // 2:])
        else:
            b = _shape_bytes(result_txt)
        if kind == "all-reduce":
            b *= 2
        out[kind] += b
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out
