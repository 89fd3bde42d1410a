"""Plain float32 reference of a dense decoder-only transformer.

Pre-norm decoder layers, as the configuration file states them:

    h = rms_norm(x) * norm1
    q, k, v = h @ wq (+ bq), h @ wk (+ bk), h @ wv (+ bv)
    q, k = rope(q), rope(k)            # first ``rotary_dim`` dims,
                                       # rotate-half pairing
    x = x + causal_gqa_softmax(q k^T / sqrt(head_dim)) v @ wo
    h = rms_norm(x) * norm2
    x = x + act(h @ w_up [, h @ w_gate]) @ w_down
    logits = (rms_norm(x) * final_norm) @ lm_head

``act`` is relu(u)^2 (``mlp: relu2``) or silu(g) * u (``mlp: swiglu``).
Everything runs in float32 at ``highest`` matmul precision; the bf16
weights are upcast one column block at a time, so no float32 copy of a
whole matrix is ever held.  With ``quant="int8"`` every weight matmul
runs as int8 x int8 -> int32 instead, and with ``quant="fp8"`` on
float8_e4m3 operands (per-row activation and per-column weight scales):
the lower-precision controls of the comparison.

Imports nothing of the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
COL_BLOCK = 8192


FP8_MAX = 448.0      # largest finite float8_e4m3fn


def _quant(x: jax.Array, axis: int, quant: str):
    top = 127.0 if quant == "int8" else FP8_MAX
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    s = jnp.where(s == 0, 1.0, s)
    if quant == "int8":
        return jnp.round(x / s).astype(jnp.int8), s
    return (x / s).astype(jnp.float8_e4m3fn), s


def mm(x: jax.Array, w: jax.Array, quant) -> jax.Array:
    """x (S, K) float32 @ w (K, N) bf16 → (S, N) float32, by column block."""
    if quant:
        xq, sx = _quant(x, 1, quant)
    out = []
    for j in range(0, w.shape[1], COL_BLOCK):
        wb = w[:, j:j + COL_BLOCK].astype(jnp.float32)
        if quant == "int8":
            wq, sw = _quant(wb, 0, quant)
            y = jax.lax.dot(xq, wq, preferred_element_type=jnp.int32)
            out.append(y.astype(jnp.float32) * sx * sw)
        elif quant == "fp8":
            # exact products of the fp8 values, summed in float32
            wq, sw = _quant(wb, 0, quant)
            y = jnp.dot(xq.astype(jnp.float32), wq.astype(jnp.float32),
                        precision=HIGHEST)
            out.append(y * sx * sw)
        else:
            out.append(jnp.dot(x, wb, precision=HIGHEST))
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rope(x, positions, rotary_dim: int, theta: float):
    """x (S, H, hd): rotate the first ``rotary_dim`` dims by halves."""
    freqs = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, jnp.float32)
                             / rotary_dim))
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    xr, xp = x[..., :rotary_dim], x[..., rotary_dim:]
    x1, x2 = jnp.split(xr, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([out, xp], axis=-1)


def attention(q, k, v):
    """Causal GQA, one KV head group at a time. q (S,H,hd), k/v (S,KV,hd)."""
    s, h, hd = q.shape
    kvh = k.shape[1]
    g = h // kvh
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * g, g, axis=1)   # (S,g,hd)
        sc = jnp.einsum("qgd,kd->gqk", qi, k[:, i], precision=HIGHEST)
        sc = jnp.where(causal[None], sc * hd ** -0.5, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("gqk,kd->qgd", p, v[:, i], precision=HIGHEST)

    out = jax.lax.map(one, jnp.arange(kvh))                      # (KV,S,g,hd)
    return out.transpose(1, 0, 2, 3).reshape(s, h, hd)


def layer(m: dict, quant, x, lp, positions):
    s = x.shape[0]
    d, h, kv, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    a = lp["attn"]
    hn = rms_norm(x, lp["norm1"]["scale"], m["norm_eps"])
    q = mm(hn, a["wq"].reshape(d, h * hd), quant).reshape(s, h, hd)
    k = mm(hn, a["wk"].reshape(d, kv * hd), quant).reshape(s, kv, hd)
    v = mm(hn, a["wv"].reshape(d, kv * hd), quant).reshape(s, kv, hd)
    if "bq" in a:
        q = q + a["bq"].astype(jnp.float32)
        k = k + a["bk"].astype(jnp.float32)
        v = v + a["bv"].astype(jnp.float32)
    q = rope(q, positions, m["rotary_dim"], m["rope_theta"])
    k = rope(k, positions, m["rotary_dim"], m["rope_theta"])
    o = attention(q, k, v).reshape(s, h * hd)
    x = x + mm(o, a["wo"].reshape(h * hd, d), quant)
    hn = rms_norm(x, lp["norm2"]["scale"], m["norm_eps"])
    mp = lp["mlp"]
    u = mm(hn, mp["w_up"], quant)
    if m["mlp"] == "relu2":
        act = jnp.square(jnp.maximum(u, 0.0))
    elif m["mlp"] == "swiglu":
        act = jax.nn.silu(mm(hn, mp["w_gate"], quant)) * u
    else:
        raise ValueError(f"unknown mlp {m['mlp']!r}")
    return x + mm(act, mp["w_down"], quant)


@functools.partial(jax.jit, static_argnums=(0, 1))
def logits_at(spec: tuple, quant, params, tokens, at):
    """Logits (len(at), V) of the sequence ``tokens`` (S,) at positions
    ``at``: every layer over the whole sequence, the head at ``at``."""
    m = dict(spec)
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = params["embed"][tokens].astype(jnp.float32)

    def body(x, lp):
        return layer(m, quant, x, lp, positions), None

    x, _ = jax.lax.scan(body, x, params["layers"]["pos0"])
    x = rms_norm(x[at], params["final_norm"]["scale"], m["norm_eps"])
    return mm(x, params["lm_head"], quant)


@functools.partial(jax.jit, static_argnums=(0,))
def _gaps(spec, params, tokens, at, picks, valid):
    """Gap below the reference's best of each row of ``picks`` (R, P)."""
    ref = logits_at(spec, None, params, tokens, at)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, picks.T, axis=-1).T          # (R, P)
    gap = jnp.where(valid[None], best[None] - got, 0.0)
    agree = jnp.sum(valid & (jnp.argmax(ref, axis=-1) == picks[0]))
    return jnp.max(gap, axis=1), jnp.sum(gap, axis=1), agree


def served_gaps(model: dict, params, prompt, served, pad_to: int,
                out_max: int, controls=()) -> dict:
    """Gaps by which the served tokens' reference logits lie below the
    reference's best, over the served positions of one request: the
    widest (``gap``) and their sum (``gap_sum``).  For each control
    precision in ``controls``, the same for the token that the control
    puts first at each of those positions (``<control>_gap``, ...)."""
    spec = tuple(sorted(model.items()))
    seq = list(prompt) + list(served[:-1])
    if len(seq) > pad_to or len(served) > out_max:
        raise ValueError(f"sequence {len(seq)}/{len(served)} exceeds "
                         f"{pad_to}/{out_max}")
    tokens = np.zeros(pad_to, np.int32)
    tokens[:len(seq)] = seq
    n = len(served)
    at = np.zeros(out_max, np.int32)
    at[:n] = len(prompt) - 1 + np.arange(n)
    picks = [np.zeros(out_max, np.int32)]
    picks[0][:n] = served
    for q in controls:
        picks.append(jnp.argmax(logits_at(spec, q, params, tokens, at),
                                axis=-1).astype(jnp.int32))
    valid = np.arange(out_max) < n
    widest, total, agree = _gaps(spec, params, tokens, at,
                                 jnp.stack([jnp.asarray(p) for p in picks]),
                                 valid)
    widest, total = np.asarray(widest), np.asarray(total)
    out = {"gap": float(widest[0]), "gap_sum": float(total[0]),
           "agree": int(agree), "tokens": n}
    for i, q in enumerate(controls, 1):
        out[f"{q}_gap"] = float(widest[i])
        out[f"{q}_gap_sum"] = float(total[i])
    return out
