"""Weights made from the seed, by the benchmark, on the device.

Every leaf is drawn from its own key, ``fold_in(seed key, crc32(leaf
name))``, and a leaf stacked over layers draws layer ``l`` from
``fold_in(that key, l)``.  So any one layer of any leaf can be made again
on its own: the program's whole tree comes from one jitted call in set-up,
and a reference that runs after the window makes each layer again from the
seed and the name, taking nothing from the program.

Values are uniform with the standard deviation the leaf's rule gives, drawn
in float32 and stored in the type the program serves them in.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds exceed 32
    bits)."""
    s = int(seed) % (1 << 64)
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, np.uint32(s >> 32))
    return jax.random.fold_in(k, np.uint32(s & 0xFFFFFFFF))


def _draw(key, shape, std, dtype):
    a = math.sqrt(3.0) * std
    return jax.random.uniform(key, shape, jnp.float32, -a, a).astype(dtype)


def leaf_key(key, name: str):
    return jax.random.fold_in(key, np.uint32(zlib.crc32(name.encode())))


def layer_leaf(key, name: str, layer: int, shape, std: float, dtype):
    """Layer ``layer`` of the stacked leaf ``name`` (``shape`` without the
    layer axis)."""
    return _draw(jax.random.fold_in(leaf_key(key, name), layer), shape, std,
                 dtype)


def flat_leaf(key, name: str, shape, std: float, dtype):
    return _draw(leaf_key(key, name), shape, std, dtype)


def make_tree(key, layout, dtype):
    """A tree from ``layout``: ``{name: (shape, layers or 0, std)}``, with
    ``/`` in a name nesting dicts.  Called under ``jax.jit``."""
    tree: dict = {}
    for name, (shape, layers, std) in layout.items():
        if layers:
            ks = jax.vmap(lambda l, _n=name: jax.random.fold_in(
                leaf_key(key, _n), l))(jnp.arange(layers, dtype=jnp.uint32))
            val = jax.vmap(lambda k, _s=tuple(shape), _d=std: _draw(
                k, _s, _d, dtype))(ks)
        else:
            val = flat_leaf(key, name, tuple(shape), std, dtype)
        node = tree
        parts = name.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = val
    return tree


def init_on_device(seed: int, layout, dtype):
    """The whole tree in one jitted call, placed on the default device."""
    return jax.jit(lambda k: make_tree(k, layout, dtype))(seed_key(seed))


def check_layout(layout: dict, defs) -> None:
    """The benchmark's weight layout names and shapes every leaf of the
    program's parameter tree, and nothing else."""
    from repro.models.params import is_def
    flat = jax.tree_util.tree_flatten_with_path(defs, is_leaf=is_def)[0]
    prog = {"/".join(k.key for k in path): tuple(d.shape) for path, d in flat}
    ours = {n: ((L,) if L else ()) + tuple(s)
            for n, (s, L, _) in layout.items()}
    if prog != ours:
        raise ValueError(f"weight layout differs from the program's: "
                         f"{sorted(set(prog.items()) ^ set(ours.items()))}")
