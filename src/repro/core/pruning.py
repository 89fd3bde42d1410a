"""Weight pruning — the static (weight) side of dual-side sparsity.

The paper does not propose a pruning algorithm; it consumes models pruned
with AGP [73] (CNN/RNN) and movement pruning [54] (BERT).  This module
provides the schedules and masks needed to *produce* that weight sparsity
inside the framework:

* :func:`magnitude_mask`      — global magnitude pruning at a target ratio.
* :func:`block_mask`          — block pruning at the TPU kernel's skip
  granularity (k-slice × output block), the structured weight sparsity
  the level-2 bitmap schedule exploits directly.
* :func:`agp_sparsity`        — Automated Gradual Pruning schedule s(t).
* :func:`structured_24_mask`  — 2:4 fine-grained structural pruning (the
  A100 sparse-tensor-core scheme the paper compares against).
* :func:`vectorwise_mask`     — vector-wise pruning of Sparse Tensor Core
  [72] (fixed ratio inside each 1×L vector) — the "Single Sparse" baseline.
* :func:`prune_tree`          — apply masks across a parameter pytree.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


def magnitude_mask(w: jax.Array, sparsity: float) -> jax.Array:
    """Keep the top-(1-sparsity) fraction by |magnitude| (per tensor)."""
    if not 0.0 <= sparsity < 1.0:
        raise ValueError(f"sparsity must be in [0,1), got {sparsity}")
    k = int(round(w.size * (1.0 - sparsity)))
    if k == w.size:
        return jnp.ones_like(w, dtype=bool)
    thresh = jnp.sort(jnp.abs(w).reshape(-1))[w.size - k - 1]
    return jnp.abs(w) > thresh


def agp_sparsity(step: int, *, s_init: float = 0.0, s_final: float = 0.9,
                 t_start: int = 0, t_end: int = 1000) -> float:
    """AGP cubic schedule: s(t) = s_f + (s_i - s_f)(1 - (t-t0)/(t1-t0))^3."""
    t = min(max(step, t_start), t_end)
    frac = (t - t_start) / max(t_end - t_start, 1)
    return s_final + (s_init - s_final) * (1.0 - frac) ** 3


def block_mask(w: jax.Array, sparsity: float,
               block: Tuple[int, int] = (128, 128)) -> jax.Array:
    """Block pruning: drop whole (bk × bn) tiles by Frobenius norm.

    The structured counterpart of :func:`magnitude_mask` at the skip
    granularity of the TPU kernel (k-slice × output block): a pruned tile
    removes an entire entry from the two-level bitmap schedule, so the
    weight-side speedup is realised by the block-skip kernel rather than
    only by element-level condensation.  w: (K, N).
    """
    if not 0.0 <= sparsity < 1.0:
        raise ValueError(f"sparsity must be in [0,1), got {sparsity}")
    k, n = w.shape
    bk, bn = block
    kt, nt = -(-k // bk), -(-n // bn)
    # tile norms one (bk, N) row slab at a time, and the (K, N) mask by
    # splitting only the major axis: neither needs a (K, N)-sized f32
    # temporary or a relayout at published widths
    slabs = jnp.pad(w, ((0, kt * bk - k), (0, nt * bn - n))).reshape(
        kt, bk, nt * bn)
    norms = jax.lax.map(lambda s: jnp.sum(jnp.square(
        s.astype(jnp.float32)).reshape(bk, nt, bn), axis=(0, 2)),
        slabs)                                                    # (Kt,Nt)
    keep = int(round(kt * nt * (1.0 - sparsity)))
    if keep >= kt * nt:
        return jnp.ones_like(w, dtype=bool)
    # rank-based keep (not a threshold compare): tied tile norms —
    # constant/quantized weights — must still keep exactly `keep` tiles
    rank = jnp.argsort(jnp.argsort(norms.reshape(-1)))
    tile_keep = (rank >= kt * nt - keep).reshape(kt, nt)          # (Kt,Nt)
    cols = jnp.repeat(tile_keep, bn, axis=1)                      # (Kt,N)
    full = jnp.broadcast_to(cols[:, None, :], (kt, bk, nt * bn))
    return full.reshape(kt * bk, nt * bn)[:k, :n]


def structured_24_mask(w: jax.Array, axis: int = -1) -> jax.Array:
    """2-out-of-4 structural mask along ``axis`` (Ampere sparse TC)."""
    w = jnp.moveaxis(w, axis, -1)
    *lead, n = w.shape
    if n % 4:
        raise ValueError(f"axis length {n} not a multiple of 4")
    g = jnp.abs(w).reshape(*lead, n // 4, 4)
    # keep the 2 largest of each group of 4
    rank = jnp.argsort(jnp.argsort(g, axis=-1), axis=-1)  # 0..3, 3=largest
    mask = (rank >= 2).reshape(*lead, n)
    return jnp.moveaxis(mask, -1, axis)


def vectorwise_mask(w: jax.Array, sparsity: float = 0.75, vec: int = 32,
                    axis: int = -1) -> jax.Array:
    """Vector-wise pruning [72]: fixed keep-count inside each 1×vec vector."""
    w = jnp.moveaxis(w, axis, -1)
    *lead, n = w.shape
    pad = (-n) % vec
    g = jnp.abs(jnp.pad(w, [*[(0, 0)] * len(lead), (0, pad)]))
    g = g.reshape(*lead, (n + pad) // vec, vec)
    keep = max(int(round(vec * (1.0 - sparsity))), 1)
    rank = jnp.argsort(jnp.argsort(g, axis=-1), axis=-1)
    mask = (rank >= vec - keep).reshape(*lead, n + pad)[..., :n]
    return jnp.moveaxis(mask, -1, axis)


def prune_tree(
    params: Any,
    sparsity: float,
    *,
    method: str = "magnitude",
    predicate: Optional[Callable[[str, jax.Array], bool]] = None,
) -> Dict[str, Any]:
    """Build a mask pytree for ``params``.

    predicate(path, leaf) selects which tensors are prunable (default: all
    leaves with ndim >= 2 — weight matrices, not biases/norms).
    """
    if predicate is None:
        predicate = lambda path, leaf: hasattr(leaf, "ndim") and leaf.ndim >= 2

    flat = jax.tree_util.tree_flatten_with_path(params)
    leaves, treedef = flat

    def mask_for(path, leaf):
        name = jax.tree_util.keystr(path)
        if not predicate(name, leaf):
            return jnp.ones_like(leaf, dtype=bool)
        if method == "magnitude":
            return magnitude_mask(leaf, sparsity)
        if method == "2:4":
            return structured_24_mask(leaf)
        if method == "vectorwise":
            return vectorwise_mask(leaf, sparsity)
        raise ValueError(f"unknown pruning method {method!r}")

    masks = [mask_for(p, l) for p, l in leaves]
    return jax.tree_util.tree_unflatten(treedef, masks)


def apply_masks(params: Any, masks: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda w, m: w * m.astype(w.dtype), params, masks)
