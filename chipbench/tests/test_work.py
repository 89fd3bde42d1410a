"""Needed operations and bytes against a hand count."""
import pytest

import harness
import plugins
import work

PEAKS = harness._json(harness.HERE / "peaks.json")["TPU v5 lite"]
NEMO = harness._json(harness.HERE / "configs" / "nemotron-4-340b.json")
FAMILY = plugins.load("models", NEMO["family"])


def kept_half(m, tile):
    """Kept tiles per layer of a 50 % tile-pruned leaf of each site."""
    out = {}
    for site, (k, n) in FAMILY.site_shapes(m).items():
        layers = 1 if site == "lm_head" else m["n_layers"]
        out[FAMILY.SITE_LEAF[site]] = [(k // tile[0]) * (n // tile[1]) // 2
                                     ] * layers
    return out


def test_mlp_up_decode_hand_count():
    m, tile = NEMO["model"], tuple(NEMO["sparsity"]["tile"])
    wk = FAMILY.Work(m, kept_half(m, tile), tile)
    # w_up is 18432 x 73728: 144 x 288 tiles of 128 x 256, half kept
    nnz = 144 * 288 // 2 * 128 * 256
    assert wk.nnz["mlp.up"] == nnz == 18432 * 73728 // 2
    rows = 8
    flops = 2 * rows * nnz
    nbytes = 2 * (nnz + rows * (18432 + 73728))
    assert wk.site_least_s("mlp.up", rows, PEAKS) == pytest.approx(
        max(flops / 197e12, nbytes / 819e9))
    # eight decode rows are bound by the weight bytes: 1.36 GB / 819 GB/s
    assert nbytes / 819e9 > flops / 197e12
    assert wk.site_least_s("mlp.up", rows, PEAKS) == pytest.approx(
        1.6611e-3, rel=1e-4)


def test_prefill_flops_hand_count():
    m, tile = NEMO["model"], tuple(NEMO["sparsity"]["tile"])
    wk = FAMILY.Work(m, kept_half(m, tile), tile)
    d, h, kv, hd, f, v = 18432, 96, 8, 192, 73728, 32000
    weights = (d * h * hd + 2 * d * kv * hd + h * hd * d + 2 * d * f) // 2
    L = 512
    attn = 4 * h * hd * L * (L + 1) // 2
    head = 2 * d * v // 2
    assert wk.prefill_flops(L) == L * 2 * weights + attn + head
    keys = [600, 40]
    assert wk.decode_flops(keys) == sum(
        2 * weights + head + 4 * h * hd * k for k in keys)


def test_prefill_calls_follow_the_engine_packing():
    assert work.prefill_calls([100, 300, 250, 200, 90, 80, 70], 256, 4) == [
        (4, 256), (2, 256), (1, 512)]
    assert work.prefill_calls([], 256, 1) == []
