"""GOOD: the dtype is pinned on the leaf, so the result does not depend
on the ambient x64 switch."""
import jax.numpy as jnp


def as_f32(x):
    return jnp.asarray(x, jnp.float32)
