"""RP301 clean twin: the same copy tiled down to a VMEM-sized block."""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

HUGE = 4096
TILE = 512


def copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def tiled_copy(x):
    # (512, 512) f32 in + out = 2 MiB resident — fits comfortably
    return pl.pallas_call(
        copy_kernel,
        grid=(HUGE // TILE, HUGE // TILE),
        in_specs=[pl.BlockSpec((TILE, TILE), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((TILE, TILE), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((HUGE, HUGE), jnp.float32),
        name="tiled_copy",
    )(x)
