"""The process-wide compile counter: one listener, a count that rises on a
new shape and stays put on a repeat."""
import jax
import jax.numpy as jnp

from repro.obs import compiles


def test_counter_rises_on_a_new_shape_and_not_on_a_repeat():
    compiles.install()
    compiles.install()                      # idempotent: one listener
    f = jax.jit(lambda x: jnp.sin(x) * 2.0)
    n0 = compiles.count()
    f(jnp.ones((5, 11))).block_until_ready()
    n1 = compiles.count()
    assert n1 > n0
    f(jnp.ones((5, 11))).block_until_ready()
    assert compiles.count() == n1
    f(jnp.ones((6, 11))).block_until_ready()
    assert compiles.count() > n1
