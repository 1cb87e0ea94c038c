"""RP304 bad fixture: a kernel launched without a name."""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

N = 512
TILE = 128


def copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def unnamed_copy(x):
    # the custom call is named after whatever jit or scope encloses it
    return pl.pallas_call(
        copy_kernel,
        grid=(N // TILE,),
        in_specs=[pl.BlockSpec((TILE, N), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((TILE, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, N), jnp.float32),
    )(x)
