"""RP304 clean twin: the kernel names itself."""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

N = 512
TILE = 128


def copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def named_copy(x):
    # the custom call is named ``row_copy`` wherever it is traced
    return pl.pallas_call(
        copy_kernel,
        grid=(N // TILE,),
        in_specs=[pl.BlockSpec((TILE, N), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((TILE, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, N), jnp.float32),
        name="row_copy",
    )(x)
