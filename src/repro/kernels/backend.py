"""Which kernels run is decided by the backend, not by flags.

Every Pallas entry point takes ``interpret: Optional[bool] = None`` and every
``ModelConfig`` kernel switch defaults to ``None``. ``None`` means: on a TPU,
the Mosaic kernels; anywhere else, the Pallas interpreter and the jnp paths.
An explicit ``True``/``False`` always wins, so tests that pin interpret mode
(or a compile against a described TPU topology, which runs with the CPU as
the default backend) keep working.
"""
from __future__ import annotations

from typing import Optional

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_mode(interpret: Optional[bool]) -> bool:
    """Pallas ``interpret=``: the explicit value, else Mosaic only on TPU."""
    return (not on_tpu()) if interpret is None else bool(interpret)


def kernels_on(flag: Optional[bool]) -> bool:
    """A ``use_pallas_*`` switch: the explicit value, else on only on TPU."""
    return on_tpu() if flag is None else bool(flag)
