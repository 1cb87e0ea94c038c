"""Device phases: the names the jitted steps give their work.

A phase is a ``jax.named_scope``. It lands in the ``op_name`` metadata of
every HLO instruction traced inside it (fusions take their root's), and from
there in the profiler's per-op ``tf_op`` stat, so a device trace can be
split by phase without timing anything on the host. Scopes are metadata
only: the compiled ops, their fusion and their arithmetic do not change, so
they are always on.

Vocabulary (``src/repro/obs/README.md``, "Device phases"):

- the robust data-parallel train step (``dist/steps.py``):
  ``robust_step/{grad_x, grad_xprev, momentum, attack, aggregate, update}``;
- inside an aggregate (``dist/robust.py``): ``anchor``, ``distance``,
  ``combine`` (ω-CTMA) and ``weiszfeld`` (ω-GM), nested under the step's
  ``aggregate``.
"""
from __future__ import annotations

import jax

ROBUST_STEP = "robust_step"
GRAD_X = f"{ROBUST_STEP}/grad_x"            # value_and_grad at x_t
GRAD_XPREV = f"{ROBUST_STEP}/grad_xprev"    # mu^2-SGD's grad at x_{t-1}
MOMENTUM = f"{ROBUST_STEP}/momentum"        # the group momenta's update
ATTACK = f"{ROBUST_STEP}/attack"            # Byzantine groups' momenta
AGGREGATE = f"{ROBUST_STEP}/aggregate"      # the robust rule
UPDATE = f"{ROBUST_STEP}/update"            # the server's weight update
ROBUST_PHASES = (GRAD_X, GRAD_XPREV, MOMENTUM, ATTACK, AGGREGATE, UPDATE)

# nested under an aggregate phase
ANCHOR = "anchor"
DISTANCE = "distance"
COMBINE = "combine"
WEISZFELD = "weiszfeld"


def phase(name: str):
    """``with phase(GRAD_X): ...`` names the device work traced inside."""
    return jax.named_scope(name)
