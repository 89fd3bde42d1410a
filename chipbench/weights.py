"""Seeded weights for a configuration, made and pruned on the device.

The benchmark's own recipe, independent of the program's initialisers:
one jitted call turns ``--seed`` into every leaf of the parameter tree
the served model takes, in the dtype it is served in, and prunes the
matrices the sparse kernels dispatch to whole (k-slice x column-block)
tiles.  The plain reference makes the same tree again from the same
seed after the program has been freed, so it never reads an array the
program held.

The tree, and which of its matrices are pruned along which axes, is the
configuration's family's (``models/<family>.py``).
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import plugins


def base_key(seed: int) -> jax.Array:
    """A raw threefry key from any non-negative integer seed."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


def as_matrices(w: jax.Array, stacked: bool, k_axes: int) -> jax.Array:
    """(layers, K, N) view of a pruned leaf: ``k_axes`` leading axes after
    the layer axis form K (layers = 1 for a leaf that is not stacked)."""
    if not stacked:
        w = w[None]
    k = int(np.prod(w.shape[1:1 + k_axes]))
    return w.reshape(w.shape[0], k, -1)


def prune_tiles(w3: jax.Array, fraction: float, tile: Tuple[int, int]
                ) -> Tuple[jax.Array, jax.Array]:
    """Zero the ``fraction`` of (bk x bn) tiles with the smallest norm in
    each (K, N) matrix of ``w3`` (layers, K, N); ties keep the later
    tile.  Returns (pruned w3, kept tiles per matrix)."""
    n_l, k, n = w3.shape
    bk, bn = tile
    if k % bk or n % bn:
        raise ValueError(f"tile {tile} does not divide matrix {(k, n)}")
    kt, nt = k // bk, n // bn
    t = w3.reshape(n_l, kt, bk, nt, bn)
    norms = jnp.sum(jnp.square(t.astype(jnp.float32)), axis=(2, 4))
    keep = int(round(kt * nt * (1.0 - fraction)))
    rank = jnp.argsort(jnp.argsort(norms.reshape(n_l, -1), axis=-1),
                       axis=-1)
    kept = (rank >= kt * nt - keep).reshape(n_l, kt, 1, nt, 1)
    w3 = jnp.where(kept, t, jnp.zeros((), t.dtype)).reshape(n_l, k, n)
    nz = jnp.sum(jnp.any(w3.reshape(n_l, kt, bk, nt, bn) != 0,
                         axis=(2, 4)), axis=(1, 2))
    return w3, nz


def _nest(flat: Dict[str, jax.Array]) -> dict:
    tree: dict = {}
    for path, x in flat.items():
        node = tree
        *parents, name = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = x
    return tree


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, family: str, spec: tuple, sparsity: tuple):
    fam = plugins.load("models", family)
    m = dict(spec)
    fraction, tile, pruned = sparsity
    flat, kept = {}, {}
    for i, (path, (shape, scale, kind)) in enumerate(
            fam.leaves(m).items()):
        z = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.bfloat16)
        if kind == "normal":
            x = z * jnp.bfloat16(scale)
        elif kind == "norm":
            x = jnp.bfloat16(1.0) + z * jnp.bfloat16(scale)
        else:
            x = z * jnp.bfloat16(scale)
        if path in pruned:
            w3, kept[path] = prune_tiles(
                as_matrices(x, fam.stacked(path), fam.K_AXES[path]),
                fraction, tile)
            x = w3.reshape(shape)
        flat[path] = x
    return _nest(flat), kept


def make(family: str, model: dict, sparsity: dict, seed: int):
    """(params, kept tiles per pruned leaf and layer) on the default
    device, from the seed, in bfloat16."""
    spec = tuple(sorted(model.items()))
    leaves = plugins.load("models", family).leaves(model)
    pruned = tuple(p for p in leaves if p in sparsity["leaves"])
    return _make(base_key(seed), family, spec,
                 (float(sparsity["fraction"]), tuple(sparsity["tile"]),
                  pruned))
