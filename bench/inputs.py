"""Signals made on the device from a seed, in one jitted call."""

from __future__ import annotations

__all__ = ["complex_normal", "seed_key"]


def seed_key(seed: int):
    """A PRNG key for any whole ``seed``, 64 bits of it kept."""
    import jax
    import numpy as np
    seed = int(seed) % (1 << 64)
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def complex_normal(n: int, seed: int, sharding):
    """An (n, n) complex64 signal, real and imaginary parts standard
    normal, laid out by ``sharding``; the same seed gives the same
    signal."""
    import jax
    import jax.numpy as jnp

    def gen(key):
        re, im = jax.random.normal(key, (2, n, n), jnp.float32)
        return jax.lax.complex(re, im)

    return jax.jit(gen, out_shardings=sharding)(seed_key(seed))
