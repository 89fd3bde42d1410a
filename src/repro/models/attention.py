"""Attention: GQA, sliding-window, cross-attention, RoPE variants, caches.

Grouped-query attention never materialises repeated KV heads (einsum with
an explicit group dim), softmax runs in f32, and long-KV attention runs
KV-chunked (flash-style running log-sum-exp via ``lax.scan``) so prefill
at 32k context keeps activation memory O(chunk) instead of O(S²).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.platform import resolve_interpret
from repro.models import cache as kvc
from repro.models import nn
from repro import sparse as sp

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def _rope_angles(positions: jax.Array, dim: int, theta: float) -> Tuple[
        jax.Array, jax.Array]:
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., dim/2)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, positions: jax.Array, style: str,
               theta: float) -> jax.Array:
    """x: (B, S, H, hd); positions: (S,) shared or (B, S) per-row
    absolute token positions (the multi-slot batched decode)."""
    if style == "none":
        return x
    hd = x.shape[-1]
    rot = hd if style == "half" else hd // 2  # chatglm "2d": half the dims
    cos, sin = _rope_angles(positions, rot, theta)  # (S|B,S, rot/2)
    if positions.ndim == 1:
        cos, sin = cos[None], sin[None]
    cos = cos[:, :, None, :].astype(x.dtype)
    sin = sin[:, :, None, :].astype(x.dtype)
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = jnp.split(xr, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([out, xp], axis=-1) if rot < hd else out


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, *, cross: bool = False):
    hd, h, kv, d = cfg.hd, cfg.n_heads, cfg.n_kv_heads, cfg.d_model
    ks = jax.random.split(key, 4)
    scale = d ** -0.5
    p = {
        "wq": nn.normal(ks[0], (d, h, hd), ("embed", "heads", "head_dim"),
                        stddev=scale),
        "wk": nn.normal(ks[1], (d, kv, hd), ("embed", "kv_heads",
                                             "head_dim"), stddev=scale),
        "wv": nn.normal(ks[2], (d, kv, hd), ("embed", "kv_heads",
                                             "head_dim"), stddev=scale),
        "wo": nn.normal(ks[3], (h, hd, d), ("heads", "head_dim", "embed"),
                        stddev=scale),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = nn.zeros((h, hd), ("heads", "head_dim"))
        p["bk"] = nn.zeros((kv, hd), ("kv_heads", "head_dim"))
        p["bv"] = nn.zeros((kv, hd), ("kv_heads", "head_dim"))
    return p


# ---------------------------------------------------------------------------
# core attention (grouped, masked, optionally KV-chunked)
# ---------------------------------------------------------------------------

def _attend_block(q, k, v, qpos, kpos, window) -> Tuple[jax.Array, jax.Array,
                                                        jax.Array]:
    """Unnormalised attention over one KV block.

    q: (B, Sq, KV, G, hd); k/v: (B, Skv, KV, hd);
    qpos: (Sq,) / kpos: (Skv,) absolute positions (-1 = invalid slot),
    each optionally batched with a (B, ·) leading dim (per-slot serving
    decode) — broadcasting keeps the shared form bit-identical.
    Returns (acc (B,Sq,KV,G,hd) f32, row max m, row sumexp l).
    """
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (q.shape[-1] ** -0.5)
    kp = kpos[..., None, :]                 # (1|B, 1, Skv)-broadcastable
    qp = qpos[..., :, None]                 # (1|B, Sq, 1)-broadcastable
    valid = (kp >= 0) & (kp <= qp)
    if window is not None:
        valid &= kp > (qp - window)
    vb = valid if valid.ndim == 3 else valid[None]      # (B|1, Sq, Skv)
    scores = jnp.where(vb[:, None, None], scores, NEG_INF)
    m = jnp.max(scores, axis=-1)                       # (B,KV,G,Sq)
    e = jnp.exp(scores - m[..., None])
    e = jnp.where(vb[:, None, None], e, 0.0)
    l = jnp.sum(e, axis=-1)
    acc = jnp.einsum("bkgqs,bskd->bqkgd", e, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return acc, jnp.moveaxis(m, 3, 1), jnp.moveaxis(l, 3, 1)  # (B,Sq,KV,G)


def attend(q: jax.Array, k: jax.Array, v: jax.Array, *,
           qpos: jax.Array, kpos: jax.Array,
           window: Optional[int] = None, chunk: int = 0,
           k_scale: Optional[jax.Array] = None,
           v_scale: Optional[jax.Array] = None) -> jax.Array:
    """Masked GQA attention.  q: (B,Sq,H,hd), k/v: (B,Skv,KVH,hd).

    chunk > 0 and Skv > chunk → scan over KV chunks with running
    log-sum-exp (activation memory O(Sq·chunk) instead of O(Sq·Skv)).
    k/v may be int8 with per-(token, head) ``k_scale``/``v_scale`` —
    dequantisation then happens per chunk inside the scan, so the full
    bf16/f32 cache copy is never materialised (the int8 KV memory win
    survives buffer assignment).
    """
    b, sq, h, hd = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, hd)

    def deq(kb, vb, ks, vs):
        if ks is None:
            return kb, vb
        kb = (kb.astype(jnp.bfloat16) * ks.astype(jnp.bfloat16))
        vb = (vb.astype(jnp.bfloat16) * vs.astype(jnp.bfloat16))
        return kb.astype(q.dtype), vb.astype(q.dtype)

    if chunk and skv > chunk and skv % chunk == 0:
        nc = skv // chunk
        ks_ = k.reshape(b, nc, chunk, kvh, hd).transpose(1, 0, 2, 3, 4)
        vs_ = v.reshape(b, nc, chunk, kvh, hd).transpose(1, 0, 2, 3, 4)
        if kpos.ndim == 1:
            kposc = kpos.reshape(nc, chunk)
        else:  # per-row key positions (paged multi-slot decode)
            kposc = kpos.reshape(b, nc, chunk).transpose(1, 0, 2)
        if k_scale is None:
            xs = (ks_, vs_, kposc)
        else:
            ksc = k_scale.reshape(b, nc, chunk, kvh, 1).transpose(
                1, 0, 2, 3, 4)
            vsc = v_scale.reshape(b, nc, chunk, kvh, 1).transpose(
                1, 0, 2, 3, 4)
            xs = (ks_, vs_, kposc, ksc, vsc)

        def step(carry, blk):
            acc, m, l = carry
            if k_scale is None:
                kb, vb, kp = blk
            else:
                kb, vb, kp, ksb, vsb = blk
                kb, vb = deq(kb, vb, ksb, vsb)
            a2, m2, l2 = _attend_block(qg, kb, vb, qpos, kp, window)
            m_new = jnp.maximum(m, m2)
            c1 = jnp.exp(m - m_new)
            c2 = jnp.exp(m2 - m_new)
            acc = acc * c1[..., None] + a2 * c2[..., None]
            l = l * c1 + l2 * c2
            return (acc, m_new, l), None

        acc0 = jnp.zeros((b, sq, kvh, g, hd), jnp.float32)
        m0 = jnp.full((b, sq, kvh, g), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, sq, kvh, g), jnp.float32)
        (acc, _, l), _ = jax.lax.scan(step, (acc0, m0, l0), xs)
    else:
        if k_scale is not None:
            k, v = deq(k, v, k_scale, v_scale)
        acc, _, l = _attend_block(qg, k, v, qpos, kpos, window)

    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, sq, h, hd).astype(q.dtype)


def attend_sparse(q: jax.Array, cache, cfg: ModelConfig, *,
                  qpos: jax.Array, kpos: jax.Array,
                  window: Optional[int] = None) -> jax.Array:
    """Bitmap-scheduled decode attention over a ``SparseKVCache``.

    q: (B, 1, H, hd).  Computes exactly the same masked-softmax GQA as
    :func:`attend`'s single-block path, but routes both matmuls through
    :func:`repro.sparse.grouped_matmul` as stacked per-(batch × kv-head)
    problems (E = B·KV), so the stats tape records scheduled-vs-skipped
    cache blocks and — with ``cfg.sparse_use_kernel`` — the ragged
    grouped Pallas kernel executes the skips (DESIGN.md §10):

    * score: ``scoresᵀ[e] = K[e] (T, hd) @ qᵀ[e] (hd, G)`` — cache slots
      are block-*rows*; the schedule is the cache occupancy bitmap ANDed
      with the causal/window mask (skipped rows get masked to -inf
      anyway, so eliding them never changes the output);
    * value: ``out[e] = p[e] (G, T) @ V[e] (T, hd)`` — cache slots are
      the *contraction* axis; unwritten blocks are genuine zero k-slices
      of V (weight side), masked history rides p's activation side.

    Matmuls accumulate in f32 (``out_dtype``) like the dense path, so the
    XLA fallback is bit-identical to :func:`attend` over the same cache.
    Decode shapes only — the O(T·G) score tensor is not KV-chunked.
    """
    from repro.sparse import plan as pln
    skvc = sp.kvcache
    b, sq, h, hd = q.shape
    t = cache.capacity
    kvh = cache.k.shape[-2]
    g = h // kvh
    ne = b * kvh

    # the cache gather and the operand build run under the ``attn.kv``
    # scope: device time of the paged view, apart from the two matmuls
    with jax.named_scope("attn.kv"):
        # dequantise / cast exactly like the dense decode branches; paged
        # caches gather their logical per-slot view first (DESIGN.md §14)
        paged = isinstance(cache, skvc.PagedSparseKVCache)
        if paged:
            kd, vd = skvc.paged_read(cache, dtype=q.dtype)
            occ = skvc.paged_occupancy_mask(cache)          # (B, T)
        elif cache.quantized:
            kd = (cache.k.astype(jnp.bfloat16)
                  * cache.k_scale.astype(jnp.bfloat16)).astype(q.dtype)
            vd = (cache.v.astype(jnp.bfloat16)
                  * cache.v_scale.astype(jnp.bfloat16)).astype(q.dtype)
            occ = skvc.occupancy_mask(cache)                # (T,)
        else:
            kd, vd, _ = kvc.read(cache, dtype=q.dtype)
            occ = skvc.occupancy_mask(cache)
        kd_e = kd.transpose(0, 2, 1, 3).reshape(ne, t, hd)
        vd_e = vd.transpose(0, 2, 1, 3).reshape(ne, t, hd)
        qw = q.reshape(b, kvh, g, hd).transpose(0, 1, 3, 2).reshape(
            ne, hd, g)

        # the decode plan: maintained occupancy AND the causal/window
        # mask.  Occupancy ≡ kpos >= 0 (property-tested), so ``sched``
        # doubles as the dense path's softmax validity mask bit-for-bit;
        # the dispatch layer derives the block-level front-pack from the
        # operand metadata.  Paged multi-slot decode carries per-row
        # positions: qpos (B, 1) and kpos (B, T) yield a per-slot (B, T)
        # schedule, expanded over the kv heads of each slot to
        # per-problem (E, T) metadata.
        qref = qpos[0] if qpos.ndim == 1 else qpos
        sched = pln.kv_decode_slots(occ, kpos, qref, window)
        if sched.ndim == 2:
            sched_e = jnp.broadcast_to(
                sched[:, None, :], (b, kvh, t)).reshape(ne, t)
            occ_e = jnp.broadcast_to(
                occ[:, None, :], (b, kvh, t)).reshape(ne, t)
        else:
            sched_e, occ_e = sched, occ
    # first-class decode tuning sites (DESIGN.md §16): attn.score keys on
    # (M=T, N=G, K=hd) — slots are block rows, so the served block_m IS
    # the slot tile — and attn.value on (M=G, N=hd, K=T) — slots are the
    # contraction axis, so the served slice_k IS the value tile.  Both
    # resolve host-side *before* operand construction (the value operand
    # metadata must be built at the served tile granularity), falling
    # back to cfg.sparse_block_t when the cache has no measurement.  f32
    # accumulation is pinned on both sites so the XLA fallback matches
    # dense attention bit-for-bit (DESIGN.md §10).
    st_s = sp.site.make("attn.score", "attn.score", out_dtype="float32")
    st_v = sp.site.make("attn.value", "attn.value", out_dtype="float32")
    kw_s = sp.site.resolve(st_s, cfg, m=t, n=g, k=hd, e=ne, dtype=q.dtype)
    kw_v = sp.site.resolve(st_v, cfg, m=g, n=hd, k=t, e=ne, dtype=q.dtype)
    # operand metadata at the granularity the dispatch will run
    interp = resolve_interpret(None)
    bt = pln.clamp_geometry(g, hd, t, kw_v["block_m"], kw_v["block_n"],
                            kw_v["slice_k"], interp)[2]
    sk_hd = pln.clamp_geometry(t, g, hd, kw_s["block_m"], kw_s["block_n"],
                               kw_s["slice_k"], interp)[2]

    with jax.named_scope("attn.kv"):
        x_k = skvc.score_operand(kd_e, sched_e, sk_hd)
    scores_t, _ = sp.site.grouped_matmul(x_k, qw, st_s, cfg,
                                         resolved=kw_s)
    scores = scores_t.reshape(b, kvh, t, g).transpose(0, 1, 3, 2)
    scores = scores[:, :, :, None, :] * (hd ** -0.5)   # (B,KV,G,1,T)

    valid = (sched[:, None, None, None, :] if sched.ndim == 2
             else sched[None, None, None, None, :])    # (B|1,1,1,1,T)
    scores = jnp.where(valid, scores, NEG_INF)
    m = jnp.max(scores, axis=-1)
    e = jnp.exp(scores - m[..., None])
    e = jnp.where(valid, e, 0.0)
    l = jnp.sum(e, axis=-1)                            # (B,KV,G,1)

    p_e = e[:, :, :, 0, :].reshape(ne, g, t)
    with jax.named_scope("attn.kv"):
        x_p, w_v = skvc.value_operands(occ_e, p_e, vd_e, sched_e, bt)
    acc_e, _ = sp.site.grouped_matmul(x_p, w_v, st_v, cfg,
                                      resolved={**kw_v, "slice_k": bt})

    acc = acc_e.reshape(b, kvh, g, hd)[:, None]        # (B,1,KV,G,hd)
    l = l.transpose(0, 3, 1, 2)                        # (B,1,KV,G)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, sq, h, hd).astype(q.dtype)


def _proj(x: jax.Array, w: jax.Array, cfg: ModelConfig, name: str,
          n_contract: int = 1, plan_act=None) -> jax.Array:
    """Head projection through the sparse dispatch layer.

    Equivalent to ``einsum("bsd,dhk->bshk")`` (n_contract=1) /
    ``einsum("bshk,hkd->bsd")`` (n_contract=2); with a non-dense
    ``cfg.sparse_mode`` the dispatch plans activation-side skips and
    records StepCounts.  ``plan_act`` is the cached weight-side slice
    activity over the flattened contraction axis (from
    ``transformer.plan_weight_activities``) — without it the weight side
    is re-reduced on the fly every call.
    """
    if cfg.sparse_mode == "dense":
        eq = "bsd,dhk->bshk" if n_contract == 1 else "bshk,hkd->bsd"
        return jnp.einsum(eq, x, w)
    axes = ("embed", "heads") if n_contract == 1 else ("heads", "embed")
    y, _ = sp.site.project(
        x, w, sp.site.make("matmul", name, axes=axes), cfg,
        n_contract=n_contract, plan_act=plan_act)
    return y


# ---------------------------------------------------------------------------
# layer forward (self / cross, with optional cache)
# ---------------------------------------------------------------------------

def attention_forward(
    params: Dict, x: jax.Array, cfg: ModelConfig, *,
    positions: jax.Array,                  # (S,) absolute positions of x
    cache: Optional[kvc.KVCache] = None,   # decode/prefill cache
    kv_source: Optional[jax.Array] = None,  # cross-attn memory (B, M, D)
    is_cross: bool = False,
    causal: bool = True,
    update_cache: bool = True,
    chunk: int = 0,
    plans: Optional[Dict] = None,
) -> Tuple[jax.Array, Optional[kvc.KVCache]]:
    """One attention layer (projections + attend + output).

    Self-attention: kv_source is None (K/V from x, RoPE applied).
    Cross-attention (is_cross): kv_source is the memory (causal=False);
    at decode the memory K/V live in a pre-filled cache
    (kv_source=None, update_cache=False).
    ``plans``: cached weight-side slice activities for wq/wk/wv/wo
    (sparse dispatch; optional).
    Returns (output (B,S,D), updated cache or None).
    """
    if is_cross:
        causal = False
    # archs whose head count doesn't divide the model axis (yi: 56,
    # whisper: 8) fall back to query-sequence sharding for attention —
    # queries are independent, so this is exact (DESIGN.md §6).
    tp_heads = nn.dim_shardable(cfg.n_heads, "heads")
    seq_ax = "seq" if tp_heads else "seq_q"
    plans = plans or {}
    q = _proj(x, params["wq"].astype(x.dtype), cfg, "attn.q",
              plan_act=plans.get("wq"))
    if "bq" in params:
        q = q + params["bq"].astype(q.dtype)
    q = nn.shard_act(q, "batch", seq_ax, "heads", None)

    k = v = None
    if kv_source is not None or cache is None or update_cache:
        src = x if kv_source is None else kv_source
        k = _proj(src, params["wk"].astype(x.dtype), cfg, "attn.k",
                  plan_act=plans.get("wk"))
        v = _proj(src, params["wv"].astype(x.dtype), cfg, "attn.v",
                  plan_act=plans.get("wv"))
        if "bk" in params:
            k = k + params["bk"].astype(k.dtype)
            v = v + params["bv"].astype(v.dtype)
        k = nn.shard_act(k, "batch", "seq", "kv_heads", None)
        v = nn.shard_act(v, "batch", "seq", "kv_heads", None)

    if not is_cross:
        q = apply_rope(q, positions, cfg.rope_style, cfg.rope_theta)
        if k is not None:
            k = apply_rope(k, positions, cfg.rope_style, cfg.rope_theta)

    window = (cfg.sliding_window or None) if causal else None
    big = jnp.int32(2 ** 30)

    if cache is not None:
        is_paged = isinstance(cache, sp.PagedSparseKVCache)
        if update_cache:
            if is_paged:
                cache = sp.kvcache.paged_update(cache, k, v)
            elif isinstance(cache, sp.SparseKVCache):
                cache = sp.kvcache.update(cache, k, v)
            else:
                cache = kvc.update(cache, k, v)
        qpos = positions if causal else jnp.full_like(positions, big)
        kpos = (sp.kvcache.paged_key_positions(cache) if is_paged
                else kvc.key_positions(cache))
        if ((is_paged or isinstance(cache, sp.SparseKVCache))
                and cfg.sparse_mode != "dense" and q.shape[1] == 1
                and causal):
            # bitmap-scheduled decode: both attention matmuls route
            # through the sparse dispatch (DESIGN.md §10)
            out = attend_sparse(q, cache, cfg, qpos=qpos, kpos=kpos,
                                window=window)
        elif is_paged:
            # dense-mode paged decode: gather the logical per-slot view
            # and run the shared masked attend (per-row positions)
            if cache.quantized:
                kp_, vp_, ksp, vsp = sp.kvcache.paged_view(cache)
                out = attend(q, kp_, vp_, qpos=qpos, kpos=kpos,
                             window=window, chunk=chunk,
                             k_scale=ksp, v_scale=vsp)
            else:
                kd, vd = sp.kvcache.paged_read(cache, dtype=x.dtype)
                out = attend(q, kd, vd, qpos=qpos, kpos=kpos,
                             window=window, chunk=chunk)
        elif cache.quantized:
            # raw int8 KV + per-chunk dequant inside attend
            out = attend(q, cache.k, cache.v, qpos=qpos, kpos=kpos,
                         window=window, chunk=chunk,
                         k_scale=cache.k_scale, v_scale=cache.v_scale)
        else:
            kd, vd, _ = kvc.read(cache, dtype=x.dtype)
            out = attend(q, kd, vd, qpos=qpos, kpos=kpos, window=window,
                         chunk=chunk)
    else:
        if causal:
            qpos, kpos = positions, positions
        else:
            qpos = jnp.full((x.shape[1],), big, jnp.int32)
            kpos = jnp.arange(k.shape[1], dtype=jnp.int32)
        out = attend(q, k, v, qpos=qpos, kpos=kpos, window=window,
                     chunk=chunk)

    out = nn.shard_act(out, "batch", seq_ax, "heads", None)
    y = _proj(out, params["wo"].astype(x.dtype), cfg, "attn.out",
              n_contract=2, plan_act=plans.get("wo"))
    return nn.shard_act(y, "batch", "seq", "embed"), cache
