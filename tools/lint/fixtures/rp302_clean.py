"""RP302 clean twin: index maps take one arg per grid axis and return one
index per block axis."""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

N = 512
TILE = 128


def copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def good_arity(x):
    return pl.pallas_call(
        copy_kernel,
        grid=(N // TILE, N // TILE),
        in_specs=[pl.BlockSpec((TILE, TILE), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((TILE, TILE), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((N, N), jnp.float32),
        name="good_arity_copy",
    )(x)
