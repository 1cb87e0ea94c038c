"""RP301 bad fixture: one block blows the 16 MiB VMEM budget."""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

HUGE = 4096


def copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def huge_block(x):
    # (4096, 4096) f32 in + out = 128 MiB resident — way over budget
    return pl.pallas_call(
        copy_kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec((HUGE, HUGE), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((HUGE, HUGE), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((HUGE, HUGE), jnp.float32),
        name="huge_copy",
    )(x)
