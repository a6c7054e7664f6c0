"""Seeded random weights, made on the device, the same for the served
program and the reference.

Every leaf of a family's ``layout`` (its reference module) is drawn from
its own key, ``fold_in(fold_in(seed_key, leaf_index), layer)``, and stored in
the served dtype. So the whole model comes out of one jitted call for the
program, and the reference draws any single layer again on its own, bit
for bit, without holding the model in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A threefry key from any non-negative whole number (wider than 32
    bits too): the seed is hashed to two 32-bit words first."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def _leaf(key, shape, init, dtype):
    kind = init[0]
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    if kind == "normal":
        return (jax.random.normal(key, shape, jnp.float32) * init[1]).astype(dtype)
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                          init[1], init[2])).astype(dtype)
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        np.log(init[1]), np.log(init[2])))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)   # softplus^-1
    raise ValueError(f"unknown init {init!r}")


def _names(ref, sizes):
    glob, layer = ref.layout(sizes)
    return list(glob), list(layer), glob, layer


def make_all(ref, sizes, key, dtype, prefix="l."):
    """Every weight, layer leaves stacked on a leading axis, named as the
    program names them (``prefix`` before layer leaves)."""
    gnames, lnames, glob, layer = _names(ref, sizes)
    n = sizes["num_layers"]
    dtype = jnp.dtype(dtype)

    @jax.jit
    def make(key):
        out = {}
        for i, name in enumerate(gnames):
            shape, init = glob[name]
            out[name] = _leaf(jax.random.fold_in(jax.random.fold_in(key, i), 0),
                              shape, init, dtype)
        for j, name in enumerate(lnames):
            shape, init = layer[name]
            kl = jax.random.fold_in(key, len(gnames) + j)
            out[prefix + name] = jax.vmap(
                lambda l: _leaf(jax.random.fold_in(kl, l), shape, init, dtype)
            )(jnp.arange(n))
        return out

    return make(key)


@functools.lru_cache(maxsize=8)
def _layer_fn(ref, sizes_items, dtype_name):
    sizes = dict(sizes_items)
    gnames, lnames, glob, layer = _names(ref, sizes)
    dtype = jnp.dtype(dtype_name)

    @jax.jit
    def make(key, l):
        return {name: _leaf(jax.random.fold_in(
                    jax.random.fold_in(key, len(gnames) + j), l),
                    *layer[name], dtype).astype(jnp.float32)
                for j, name in enumerate(lnames)}

    @jax.jit
    def make_glob(key):
        return {name: _leaf(jax.random.fold_in(jax.random.fold_in(key, i), 0),
                            *glob[name], dtype).astype(jnp.float32)
                for i, name in enumerate(gnames)}

    return make, make_glob


def layer_f32(ref, sizes, key, dtype, l):
    """Layer ``l``'s leaves, the served values widened to float32."""
    make, _ = _layer_fn(ref, tuple(sorted(sizes.items())), jnp.dtype(dtype).name)
    return make(key, l)


def globals_f32(ref, sizes, key, dtype):
    _, make_glob = _layer_fn(ref, tuple(sorted(sizes.items())),
                             jnp.dtype(dtype).name)
    return make_glob(key)
