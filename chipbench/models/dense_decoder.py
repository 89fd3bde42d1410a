"""The dense decoder-only family, as this benchmark describes it.

A configuration names its family (``"family"`` in ``configs/<name>.json``)
and the benchmark reads from this file, by that name, everything that
depends on the family: the parameter tree the served model takes, which
matrices the sparse kernels dispatch and how they are viewed as (K, N),
the needed work of a served step, and what the program's own config
must say for the benchmark to describe it.  A family with other leaves
(experts, a sliding window, another norm) is a new file beside this one,
with its reference in ``references/``.

Tree layout (what the program's transformer forward reads for a dense
decoder): ``embed (V, d)``, ``lm_head (d, V)``, ``final_norm.scale``,
and under ``layers.pos0`` every per-layer leaf stacked over the layers.
Leaves under ``layers/`` carry the layer axis first; the others do not.

Needed work is counted from the shapes the benchmark dispatched and from
the pruned weights' nonzero tiles, with activations counted dense: the
same count whatever implements a site, so a site that a later change
moves between a kernel and XLA keeps its work.  bf16 operands (2 bytes).
Sites per layer, as (K, N) of the matrix multiplied: attn.q (d, H*hd),
attn.k / attn.v (d, KV*hd), attn.out (H*hd, d), mlp.up / mlp.gate
(d, f), mlp.down (f, d); once per step lm_head (d, V).  Decode attention
over the cache is attn.score and attn.value: per layer and token with
``keys`` positions in its cache, 2*keys*H*hd operations each, and the K
and V rows read once (keys*KV*hd*2 bytes each).
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

BYTES = 2


def leaves(m: dict) -> Dict[str, Tuple[tuple, float, str]]:
    """path → (shape, scale, kind) of every leaf, in a fixed order.

    kind: "normal" (scale = standard deviation), "norm" (1 + scale * N),
    "bias" (scale * N).
    """
    L, d, h, kv, hd, f, v = (m["n_layers"], m["d_model"], m["n_heads"],
                             m["n_kv_heads"], m["head_dim"], m["d_ff"],
                             m["vocab_size"])
    out = {
        "embed": ((v, d), 0.02, "normal"),
        "lm_head": ((d, v), 0.02, "normal"),
        "final_norm/scale": ((d,), 0.1, "norm"),
        "layers/pos0/norm1/scale": ((L, d), 0.1, "norm"),
        "layers/pos0/attn/wq": ((L, d, h, hd), d ** -0.5, "normal"),
        "layers/pos0/attn/wk": ((L, d, kv, hd), d ** -0.5, "normal"),
        "layers/pos0/attn/wv": ((L, d, kv, hd), d ** -0.5, "normal"),
        "layers/pos0/attn/wo": ((L, h, hd, d), (h * hd) ** -0.5, "normal"),
        "layers/pos0/norm2/scale": ((L, d), 0.1, "norm"),
        "layers/pos0/mlp/w_up": ((L, d, f), d ** -0.5, "normal"),
        "layers/pos0/mlp/w_down": ((L, f, d), f ** -0.5, "normal"),
    }
    if m["mlp"] == "swiglu":
        out["layers/pos0/mlp/w_gate"] = ((L, d, f), d ** -0.5, "normal")
    if m["qkv_bias"]:
        out["layers/pos0/attn/bq"] = ((L, h, hd), 0.1, "bias")
        out["layers/pos0/attn/bk"] = ((L, kv, hd), 0.1, "bias")
        out["layers/pos0/attn/bv"] = ((L, kv, hd), 0.1, "bias")
    return out


# leaf of a prunable matrix → number of its leading axes (after the layer
# axis, if any) that form K in the matrix the program dispatches (wo
# contracts heads and head_dim)
K_AXES = {
    "layers/pos0/attn/wq": 1, "layers/pos0/attn/wk": 1,
    "layers/pos0/attn/wv": 1, "layers/pos0/attn/wo": 2,
    "layers/pos0/mlp/w_up": 1, "layers/pos0/mlp/w_gate": 1,
    "layers/pos0/mlp/w_down": 1, "lm_head": 1,
}

SITE_LEAF = {
    "attn.q": "layers/pos0/attn/wq", "attn.k": "layers/pos0/attn/wk",
    "attn.v": "layers/pos0/attn/wv", "attn.out": "layers/pos0/attn/wo",
    "mlp.up": "layers/pos0/mlp/w_up", "mlp.gate": "layers/pos0/mlp/w_gate",
    "mlp.down": "layers/pos0/mlp/w_down", "lm_head": "lm_head",
}


def stacked(path: str) -> bool:
    """Whether the leaf carries the layer axis first."""
    return path.startswith("layers/")


def site_shapes(m: dict) -> Dict[str, Tuple[int, int]]:
    d, h, kv, hd, f, v = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                          m["head_dim"], m["d_ff"], m["vocab_size"])
    out = {"attn.q": (d, h * hd), "attn.k": (d, kv * hd),
           "attn.v": (d, kv * hd), "attn.out": (h * hd, d),
           "mlp.up": (d, f), "mlp.down": (f, d), "lm_head": (d, v)}
    if m["mlp"] == "swiglu":
        out["mlp.gate"] = (d, f)
    return out


def program_mismatches(cfg, m: dict) -> dict:
    """What the program's ModelConfig ``cfg`` says that the configuration
    file's sizes ``m`` (which this family and its reference describe) do
    not: name → (program, file).  Empty when they agree."""
    have = {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.hd, "d_ff": cfg.d_ff,
            "vocab_size": cfg.vocab_size, "mlp": cfg.mlp_type,
            "qkv_bias": cfg.qkv_bias, "rope_theta": cfg.rope_theta,
            "norm_eps": cfg.norm_eps,
            "rotary_dim": cfg.hd if cfg.rope_style == "half" else cfg.hd // 2}
    bad = {k: (v, m[k]) for k, v in have.items() if m[k] != v}
    # the family (and its reference) has RMS norm and full causal
    # attention only
    if cfg.norm_kind != "rms":
        bad["norm_kind"] = (cfg.norm_kind, "rms")
    if cfg.sliding_window:
        bad["sliding_window"] = (cfg.sliding_window, None)
    return bad


class Work:
    """Needed operations and bytes of one model's served steps.

    ``kept`` maps a pruned leaf to its kept tiles per layer (from
    ``weights.make``); a leaf not in it is dense.
    """

    def __init__(self, m: dict, kept: Dict[str, List[int]],
                 tile: Tuple[int, int]):
        self.m = m
        self.shapes = site_shapes(m)
        self.layers = m["n_layers"]
        tile_elems = tile[0] * tile[1]
        self.nnz: Dict[str, int] = {}   # nonzero weights, all layers
        for site, (k, n) in self.shapes.items():
            leaf = SITE_LEAF[site]
            if leaf in kept:
                self.nnz[site] = int(sum(kept[leaf])) * tile_elems
            else:
                self.nnz[site] = k * n * self.site_layers(site)

    def site_layers(self, site: str) -> int:
        return self.layers if stacked(SITE_LEAF[site]) else 1

    # -- whole-model needed operations (the mfu numerators) -------------
    def layer_flops(self) -> int:
        """Weight operations of one token through every layer."""
        return sum(2 * n for s, n in self.nnz.items() if s != "lm_head")

    def attention_flops(self, keys: int) -> int:
        """Score and value operations of one token over ``keys`` keys,
        all layers."""
        m = self.m
        return 4 * keys * m["n_heads"] * m["head_dim"] * self.layers

    def prefill_flops(self, length: int) -> int:
        """One prompt of ``length`` tokens: every layer for every token,
        causal attention, the head for the last token only."""
        # token p attends to p + 1 keys: sum over p = L (L + 1) / 2 keys
        attn = self.attention_flops(length * (length + 1) // 2)
        return (length * self.layer_flops() + attn
                + 2 * self.nnz["lm_head"])

    def decode_flops(self, keys: Iterable[int]) -> int:
        """One decode step of tokens attending to ``keys`` positions each."""
        keys = list(keys)
        return sum(self.layer_flops() + 2 * self.nnz["lm_head"]
                   + self.attention_flops(k) for k in keys)

    # -- least time of the work the kernels run -------------------------
    def site_least_s(self, site: str, rows: int, peaks: dict) -> float:
        """Least time of one projection over ``rows`` dispatched rows, all
        layers: the larger of operations over peak and bytes over
        bandwidth."""
        k, n = self.shapes[site]
        flops = 2 * rows * self.nnz[site]
        nbytes = BYTES * (self.nnz[site]
                          + self.site_layers(site) * rows * (k + n))
        return max(flops / peaks["bf16_flops_per_s"],
                   nbytes / peaks["hbm_bytes_per_s"])

    def cache_attention_least_s(self, keys: Iterable[int],
                                peaks: dict) -> float:
        """Least time of decode attention over the cache (score and value
        sites), all layers, for tokens with ``keys`` positions each."""
        m = self.m
        total_keys = sum(keys)
        flops = self.attention_flops(total_keys)
        nbytes = (2 * BYTES * total_keys * m["n_kv_heads"] * m["head_dim"]
                  * self.layers)
        return max(flops / peaks["bf16_flops_per_s"],
                   nbytes / peaks["hbm_bytes_per_s"])

    def kernels_least_s(self, sites: Iterable[str], rows: int,
                        keys: Iterable[int], peaks: dict) -> float:
        """Least time of one step's kernel sites: projections over
        ``rows`` dispatched rows, cache attention over ``keys``."""
        total = 0.0
        for s in sites:
            if s in self.shapes:
                total += self.site_least_s(s, rows, peaks)
        if "attn.score" in sites:
            total += self.cache_attention_least_s(keys, peaks)
        return total
