"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Interpret mode — how every other kernel test runs here — accepts blocks
that Mosaic refuses (unaligned slices, too much VMEM or SMEM, gathers it
cannot lower).  These tests hand each kernel to the TPU compiler at the
shapes the served path runs: nemotron-4-340b's MLP projections
(18432 <-> 73728) and its decode-attention sites (E = 4 slots x 8 KV
heads, T = 2048 positions, G = 12 query heads per KV head, head dim
192) at the tiles ``chip_smoke.py`` serves (block_m 512, block_n 256,
slice_k 128, 32-slot pages), and the conv kernels at whisper's mel stem.
Nothing runs; a compile that passes is not a chip run.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and the suite runs under
several workers.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bitmap_spgemm as bsk
from repro.kernels import grouped_spgemm as gsk
from repro.kernels.bitmap_encode import bitmap_encode_pallas
from repro.kernels.sparse_im2col import sparse_im2col_pallas
from repro.sparse import plan as pln

TILES = (512, 256, 128)            # block_m, block_n, slice_k
PAGE = 32                          # KV slots per page (sparse_block_t)
D, F = 18432, 73728                # nemotron-4-340b d_model, d_ff
E, T, G, HD = 4 * 8, 2048, 12, 192
MEL, FRAMES, WHISPER_D = 80, 3002, 512   # mel bins, padded frames, width


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    lowered = jax.jit(fn).lower(*args)
    assert "tpu_custom_call" in lowered.as_text()
    return lowered.compile()


def _geometry(m, n, k, block_m, slice_k=TILES[2]):
    bm, bn, sk = pln.clamp_geometry(m, n, k, block_m, TILES[1], slice_k,
                                    False)
    return bm, bn, sk, -(-m // bm), -(-n // bn), -(-k // sk)


@pytest.mark.parametrize("m,k,n", [(4, D, F), (4096, F, D)],
                         ids=["up-decode", "down-prefill"])
def test_bitmap_spgemm_compiles(one_chip, m, k, n):
    bm, bn, sk, mt, nt, s = _geometry(m, n, k, TILES[0])
    _compile(lambda a, b, ks, c: bsk.bitmap_spgemm_planned(
        a, b, ks, c, block_m=bm, block_n=bn, slice_k=sk),
        _spec(one_chip, (m, k), jnp.bfloat16),
        _spec(one_chip, (k, n), jnp.bfloat16),
        _spec(one_chip, (mt, nt, s), jnp.int32),
        _spec(one_chip, (mt, nt), jnp.int32))


@pytest.mark.parametrize("m,k,n", [(4096, D, F), (4, F, D)],
                         ids=["up-prefill", "down-decode"])
def test_bitmap_spgemm_kfused_compiles(one_chip, m, k, n):
    bm, bn, sk, mt, nt, s = _geometry(m, n, k, TILES[0])
    assert pln.kfused_panel_bytes(bm, bn, k, sk, 2) <= pln.VMEM_BYTES
    _compile(lambda a, b, gk, c: bsk.bitmap_spgemm_kfused_planned(
        a, b, gk, c, block_m=bm, block_n=bn, slice_k=sk),
        _spec(one_chip, (m, k), jnp.bfloat16),
        _spec(one_chip, (k, n), jnp.bfloat16),
        _spec(one_chip, (mt, nt, s, sk), jnp.int32),
        _spec(one_chip, (mt, nt), jnp.int32))


def test_grouped_spgemm_compiles_at_attn_score(one_chip):
    # scoresᵀ[e] = K[e] (T, hd) @ qᵀ[e] (hd, G): slots are block rows
    bm, bn, sk, mt, nt, s = _geometry(T, G, HD, PAGE)
    _compile(lambda a, b, ks, c: gsk.grouped_spgemm_planned(
        a, b, ks, c, block_m=bm, block_n=bn, slice_k=sk,
        out_dtype=jnp.float32),
        _spec(one_chip, (E, T, HD), jnp.bfloat16),
        _spec(one_chip, (E, HD, G), jnp.bfloat16),
        _spec(one_chip, (E, mt, nt, s), jnp.int32),
        _spec(one_chip, (E, mt, nt), jnp.int32))


def test_grouped_spgemm_kfused_compiles_at_attn_value(one_chip):
    # out[e] = p[e] (G, T) @ V[e] (T, hd): slots are the contraction,
    # and the 32-slot page widens to a lane-aligned 128-slot slice
    bm, bn, sk, mt, nt, s = _geometry(G, HD, T, TILES[0], slice_k=PAGE)
    assert sk == pln.LANE
    _compile(lambda a, b, gk, c: gsk.grouped_spgemm_kfused_planned(
        a, b, gk, c, block_m=bm, block_n=bn, slice_k=sk,
        out_dtype=jnp.float32),
        _spec(one_chip, (E, G, T), jnp.bfloat16),
        _spec(one_chip, (E, T, HD), jnp.bfloat16),
        _spec(one_chip, (E, mt, nt, s, sk), jnp.int32),
        _spec(one_chip, (E, mt, nt), jnp.int32))


def test_bitmap_encode_compiles(one_chip):
    _compile(lambda x: bitmap_encode_pallas(x),
             _spec(one_chip, (MEL, 1, FRAMES), jnp.bfloat16))


@pytest.mark.parametrize("stride,c", [(1, MEL), (2, WHISPER_D)],
                         ids=["stem1", "stem2"])
def test_sparse_im2col_compiles(one_chip, stride, c):
    words = -(-FRAMES // 32)
    _compile(lambda v, b: sparse_im2col_pallas(v, b, kh=1, kw=3,
                                               stride=stride),
             _spec(one_chip, (c, 1, FRAMES), jnp.bfloat16),
             _spec(one_chip, (c, 1, words), jnp.uint32))
