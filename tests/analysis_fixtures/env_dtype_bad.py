"""BAD: env-dependent-dtype — touching the x64 switch makes numeric
results depend on ambient process config."""
import jax


def enable_precision():
    jax.config.update("jax_enable_x64", True)


def wants_x64():
    return jax.config.jax_enable_x64
