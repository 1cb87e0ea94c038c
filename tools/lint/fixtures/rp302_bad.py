"""RP302 bad fixture: index-map arity disagrees with the grid rank."""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

N = 512
TILE = 128


def copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def bad_arity(x):
    return pl.pallas_call(
        copy_kernel,
        grid=(N // TILE, N // TILE),                      # rank 2
        in_specs=[pl.BlockSpec((TILE, TILE), lambda i: (i, 0))],   # 1 arg
        out_specs=pl.BlockSpec((TILE, TILE), lambda i, j: (i,)),   # 1 index
        out_shape=jax.ShapeDtypeStruct((N, N), jnp.float32),
        name="bad_arity_copy",
    )(x)
