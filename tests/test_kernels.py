"""Pallas kernels vs. jnp oracles (interpret=True on CPU), sweeping
shapes/dtypes per the deliverable."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import (
    VMEM_LIMIT_BYTES,
    attention_tiles,
    block_needs_mask,
    flash_attention_bhsd,
    kv_block_index,
    live_kv_blocks,
    vmem_bytes,
)
from repro.kernels.flash_decode import flash_decode_bhd
from repro.kernels import moe_gmm as gmm_mod
from repro.kernels.selective_scan import selective_scan_bqnc


def rnd(key, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(key), shape,
                             jnp.float32).astype(dtype)


TOLS = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
# flash attention (prefill)
# ---------------------------------------------------------------------------

ATTN_CASES = [
    # (B, H, Kv, Sq, Skv, D, causal, window, prefix)
    (1, 4, 4, 128, 128, 64, True, None, 0),
    (2, 4, 2, 256, 256, 64, True, None, 0),          # GQA
    (1, 8, 1, 128, 128, 128, True, None, 0),         # MQA (paligemma-like)
    (2, 4, 4, 192, 192, 64, True, None, 0),          # non-multiple of block
    (1, 4, 4, 128, 128, 64, False, None, 0),         # bidirectional (enc)
    (1, 4, 4, 256, 256, 64, True, 96, 0),            # sliding window
    (1, 4, 4, 128, 128, 64, True, None, 32),         # prefix-LM
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(case, dtype):
    B, H, Kv, Sq, Skv, D, causal, window, prefix = case
    q = rnd(1, (B, H, Sq, D), dtype)
    k = rnd(2, (B, Kv, Skv, D), dtype)
    v = rnd(3, (B, Kv, Skv, D), dtype)
    got = flash_attention_bhsd(
        q, k, v, causal=causal, window=window, prefix_len=prefix,
        block_q=64, block_kv=64, interpret=True,
    )
    want = ref.flash_attention_ref(
        q, k, v, causal=causal, window=window, prefix_len=prefix
    )
    np.testing.assert_allclose(
        got.astype(np.float32), want.astype(np.float32),
        atol=TOLS[dtype], rtol=TOLS[dtype],
    )


# command-r-35b's heads (H 64, Kv 8, D 128) and an MQA group, bf16, under
# the tiles the kernel picks (bq 256 / bkv 1024; bq 32 for G 64)
SHAPE_CASES = [
    # (H, Kv, S, window, prefix)
    (64, 8, 1024, None, 0),      # 4 q tiles, one KV tile
    (64, 8, 1100, None, 0),      # S a multiple of neither tile
    (64, 8, 2100, 300, 0),       # late q tiles start past KV tile 0
    (64, 8, 2100, None, 1100),   # prefix zone reaches into KV tile 1
    (64, 1, 1100, None, 0),      # MQA: 64 heads fold into 2048 rows
]


@pytest.mark.parametrize("case", SHAPE_CASES)
def test_flash_attention_chosen_tiles_match_ref(case):
    H, Kv, S, window, prefix = case
    dtype, D, G = jnp.bfloat16, 128, H // Kv
    q = rnd(13, (1, H, S, D), dtype)
    k = rnd(14, (1, Kv, S, D), dtype)
    v = rnd(15, (1, Kv, S, D), dtype)
    got = flash_attention_bhsd(q, k, v, causal=True, window=window,
                               prefix_len=prefix, interpret=True)
    # the oracle one KV group at a time, to bound its S x S score tensor
    want = jnp.concatenate([
        ref.flash_attention_ref(
            q[:, g * G:(g + 1) * G], k[:, g:g + 1], v[:, g:g + 1],
            causal=True, window=window, prefix_len=prefix,
        ) for g in range(Kv)
    ], axis=1)
    np.testing.assert_allclose(
        got.astype(np.float32), want.astype(np.float32),
        atol=TOLS[dtype], rtol=TOLS[dtype],
    )


@pytest.mark.parametrize("S,G,D,tiles", [
    (8192, 8, 128, (256, 1024)),    # replica.prefill
    (512, 8, 128, (256, 512)),      # replica.decode's prefill
    (8192, 1, 128, (256, 1024)),    # MHA: q tile capped at 256
    (8192, 64, 128, (32, 1024)),    # MQA: 2048 folded rows
    (8192, 16, 256, (128, 1024)),
    (8192, 8, 512, (128, 1024)),    # VMEM halves the q tile
    (300, 8, 128, (256, 384)),      # KV tile rounded up to the lanes
    (100, 8, 64, (112, 128)),       # q tile rounded up to 16 rows
])
def test_attention_tiles(S, G, D, tiles):
    bq, bkv = attention_tiles(S, S, G, D)
    assert (bq, bkv) == tiles
    assert bq % 16 == 0 and bkv % 128 == 0
    assert vmem_bytes(G * bq, bkv, D, 4) <= VMEM_LIMIT_BYTES


def test_attention_tiles_at_replica_prefill():
    """B4, S 8192, 64 heads over 8 KV heads: 8,192 grid steps (the
    per-head 128 x 128 grid had 1,048,576), in 26 MiB of VMEM in bf16
    and 33 MiB in f32, against a 48 MiB limit."""
    bq, bkv = attention_tiles(8192, 8192, 8, 128)
    assert 4 * 8 * (8192 // bq) * (8192 // bkv) == 8_192
    assert vmem_bytes(8 * bq, bkv, 128, 2) == 27_262_976
    assert vmem_bytes(8 * bq, bkv, 128, 4) == 34_603_008
    assert VMEM_LIMIT_BYTES == 48 << 20


MASKINGS = {
    "causal": dict(causal=True, window=None, prefix_len=0),
    "window": dict(causal=True, window=700, prefix_len=0),
    "prefix": dict(causal=True, window=None, prefix_len=1100),
    "window_prefix": dict(causal=True, window=700, prefix_len=1100),
    "bidirectional": dict(causal=False, window=None, prefix_len=0),
}


@pytest.mark.parametrize("name", MASKINGS)
def test_dead_kv_steps_reuse_the_resident_tile(name):
    """Over every q block of a 3000-position call (bq 256, bkv 512): each
    block with an unmasked element is live, a live block not flagged as an
    edge has none masked, and along the KV axis the tile index changes only
    at live steps, each live tile named once — a dead step names the tile
    already resident, so Pallas copies nothing for it."""
    m = MASKINGS[name]
    S, bq, bkv = 3000, 256, 512
    nq, nkv = -(-S // bq), -(-S // bkv)
    tiling = dict(block_q=bq, block_kv=bkv, **m)
    qp, kp = np.arange(nq * bq)[:, None], np.arange(nkv * bkv)[None, :]
    mask = (qp < S) & (kp < S)
    if m["causal"]:
        mask &= (qp >= kp) | (kp < m["prefix_len"])
    if m["window"] is not None:
        mask &= qp - kp < m["window"]
    dead = 0
    for i in range(nq):
        lo, hi = (int(x) for x in live_kv_blocks(i, n_kv=nkv, **tiling))
        idx = [int(kv_block_index(i, j, n_kv=nkv, **tiling))
               for j in range(nkv)]
        for j in range(nkv):
            blk = mask[i * bq:(i + 1) * bq, j * bkv:(j + 1) * bkv]
            live = lo <= j <= hi
            if blk.any():
                assert live, (i, j)
            if live and not bool(block_needs_mask(i, j, seq_kv=S,
                                                  **tiling)):
                assert blk[:S - i * bq].all(), (i, j)
            if not live:
                dead += 1
                assert idx[j] == (idx[j - 1] if j else lo), (i, j)
        assert idx[0] == lo and sorted(set(idx)) == list(range(lo, hi + 1))
    assert dead > 0 or not m["causal"]


def test_flash_attention_model_layout_wrapper():
    B, S, H, D = 2, 128, 4, 64
    q = rnd(4, (B, S, H, D), jnp.float32)
    k = rnd(5, (B, S, H, D), jnp.float32)
    v = rnd(6, (B, S, H, D), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True, interpret=True)
    want = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# flash decode
# ---------------------------------------------------------------------------

DECODE_CASES = [
    (1, 4, 4, 256, 64, 256),     # full cache
    (2, 8, 2, 512, 64, 300),     # GQA + partial validity
    (1, 8, 1, 1024, 128, 700),   # MQA long cache
    (2, 4, 4, 384, 64, 100),     # short occupancy
]

#: layers of the stacked caches the decode tests read from
DECODE_LAYERS = 3


def _decode_stack(B, Kv, S, D, dtype):
    """(k, v) stacks of DECODE_LAYERS layers, each layer its own draw."""
    shape = (DECODE_LAYERS, B, Kv, S, D)
    return rnd(8, shape, dtype), rnd(9, shape, dtype)


@pytest.mark.parametrize("case", DECODE_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_matches_ref(case, dtype):
    B, H, Kv, S, D, n_valid = case
    q = rnd(7, (B, H, D), dtype)
    k, v = _decode_stack(B, Kv, S, D, dtype)
    valid = (jnp.arange(S)[None, :] < n_valid).astype(jnp.int8)
    valid = jnp.broadcast_to(valid, (B, S))
    for layer in range(DECODE_LAYERS):
        got = flash_decode_bhd(q, k, v, valid, jnp.int32(layer),
                               block_kv=128, interpret=True)
        want = ref.flash_decode_ref(q, k[layer], v[layer], valid)
        np.testing.assert_allclose(
            got.astype(np.float32), want.astype(np.float32),
            atol=TOLS[dtype], rtol=TOLS[dtype],
        )


def _decode_mask(kind, B, S):
    """(B, S) validity: every slot; a prefix per row (a cache filling
    up); or a ring that wrapped, each row live from its own slot on and
    again from slot 0 (the oldest entries overwritten)."""
    slot = jnp.arange(S)[None, :]
    if kind == "full":
        return jnp.ones((B, S), bool)
    ends = jnp.array([S // 3, S - 5, 1, S // 2])[:B, None]
    if kind == "partial":
        return slot < ends
    return (slot >= ends) | (slot < ends // 2)


@pytest.mark.parametrize("mask", ["full", "partial", "ring"])
@pytest.mark.parametrize("H,Kv", [(8, 1), (4, 4)], ids=["G8", "G1"])
def test_flash_decode_reads_the_indexed_layer(H, Kv, mask):
    """Through the model-layout wrapper, with 512-row KV blocks: each
    layer of the stack gives that layer's attention, which differs from
    every other layer's, so reading the wrong layer fails."""
    B, S, D = 4, 1024, 128
    q = rnd(7, (B, 1, H, D), jnp.float32)
    k, v = _decode_stack(B, Kv, S, D, jnp.float32)
    valid = _decode_mask(mask, B, S)
    want = [ref.flash_decode_ref(q[:, 0], k[i], v[i], valid)
            for i in range(DECODE_LAYERS)]
    for layer in range(DECODE_LAYERS):
        got = ops.flash_decode(q, k, v, jnp.int32(layer), kv_valid=valid,
                               interpret=True)[:, 0]
        np.testing.assert_allclose(got, want[layer], atol=2e-5, rtol=2e-5)
        for other in range(DECODE_LAYERS):
            if other != layer:
                assert float(jnp.abs(got - want[other]).max()) > 0.1


@pytest.mark.parametrize("S,block", [(256, 256), (1024, 512), (8704, 512),
                                     (640, 128), (544, 544), (136, 136)])
def test_flash_decode_kv_block(S, block):
    """KV blocks divide the cache, so no caller pads the stack: 512 rows
    where they divide it, a smaller multiple of 128 where that does, else
    the whole cache as one block."""
    from repro.kernels.flash_decode import kv_block

    assert kv_block(S, 512) == block


def test_flash_decode_undivided_cache_matches_ref():
    """544 slots (no multiple of 128 divides them) read as one block."""
    B, H, Kv, S, D = 2, 8, 2, 544, 64
    q = rnd(7, (B, H, D), jnp.float32)
    k, v = _decode_stack(B, Kv, S, D, jnp.float32)
    valid = _decode_mask("partial", B, S)
    got = flash_decode_bhd(q, k, v, valid, jnp.int32(2), interpret=True)
    want = ref.flash_decode_ref(q, k[2], v[2], valid)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# selective scan
# ---------------------------------------------------------------------------

SCAN_CASES = [
    # (B, Q, C, N, block_q)
    (1, 32, 64, 16, 8),   # state carried across time blocks
    (2, 64, 128, 16, 16),
    (2, 17, 256, 8, 17),  # odd chunk length
]


@pytest.mark.parametrize("case", SCAN_CASES)
def test_selective_scan_matches_ref(case):
    B, Q, C, N, block_q = case
    # a in (0,1) like exp(delta·A); b small
    a = jax.nn.sigmoid(rnd(10, (B, Q, C, N), jnp.float32))
    b = rnd(11, (B, Q, C, N), jnp.float32) * 0.1
    h0 = rnd(12, (B, C, N), jnp.float32)
    got = selective_scan_bqnc(
        a.transpose(0, 1, 3, 2), b.transpose(0, 1, 3, 2),
        h0.transpose(0, 2, 1), block_c=64, block_q=block_q, interpret=True,
    ).transpose(0, 1, 3, 2)
    want = ref.selective_scan_ref(a, b, h0)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_selective_scan_equals_mamba_chunked_path():
    """The kernel slots into mamba1_full's chunk loop: same h sequence."""
    a = jax.nn.sigmoid(rnd(13, (1, 16, 32, 8), jnp.float32))
    b = rnd(14, (1, 16, 32, 8), jnp.float32) * 0.1
    h0 = jnp.zeros((1, 32, 8), jnp.float32)

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, bl * ar + br

    a_s, b_s = jax.lax.associative_scan(combine, (a, b), axis=1)
    want = b_s + a_s * h0[:, None]
    got = ops.selective_scan(a, b, h0, interpret=True)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# MoE grouped matmul
# ---------------------------------------------------------------------------

GMM_CASES = [
    (4, 64, 128, 256),
    (8, 96, 200, 64),       # dims not multiples of 128: whole-dim blocks
    (2, 256, 512, 512),
]


@pytest.mark.parametrize("case", GMM_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm_matches_ref(case, dtype):
    """E groups of C rows each (the capacity layout) through the ragged
    kernel."""
    E, C, D, F = case
    x = rnd(15, (E * C, D), dtype)
    w = rnd(16, (E, D, F), dtype)
    sizes = jnp.full((E,), C, jnp.int32)
    got = ops.moe_gmm(x, w, sizes, block_m=32, interpret=True)
    want = ref.moe_gmm_ref(x, w, sizes)
    np.testing.assert_allclose(
        got.astype(np.float32), want.astype(np.float32),
        atol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
        rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
    )


def test_moe_ffn_matches_dense_path():
    """ops.moe_ffn == the model's einsum expert FFN."""
    E, C, D, F = 4, 32, 64, 96
    xe = rnd(17, (E, C, D), jnp.float32)
    wi = rnd(18, (E, D, F), jnp.float32)
    wg = rnd(19, (E, D, F), jnp.float32)
    wo = rnd(20, (E, F, D), jnp.float32)
    got = ops.moe_ffn(xe, wi, wg, wo, act="silu", interpret=True)
    h = jnp.einsum("ecd,edf->ecf", xe, wi)
    g = jnp.einsum("ecd,edf->ecf", xe, wg)
    want = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, wo)
    np.testing.assert_allclose(got, want, atol=1e-3, rtol=1e-3)


# padded group sizes (multiples of the 16-row tile) with empty groups,
# groups of one tile and runs of several, then dead tiles past them
RAGGED_CASES = [
    ([0, 16, 48, 0, 16], 160),
    ([32, 0, 0, 0], 64),
    ([0, 0, 0], 48),                 # nothing routed here
    ([16] * 6, 96),
]


@pytest.mark.parametrize("sizes,M", RAGGED_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ragged_moe_gmm_matches_grouped_product(sizes, M, dtype):
    G, D, F = len(sizes), 64, 96
    x = rnd(21, (M, D), dtype)
    w = rnd(22, (G, D, F), dtype)
    g = jnp.asarray(sizes, jnp.int32)
    live = sum(sizes)
    got = ops.moe_gmm(x, w, g, block_m=16, interpret=True)[:live]
    want = ref.moe_gmm_ref(x, w, g)[:live]
    np.testing.assert_allclose(
        got.astype(np.float32), want.astype(np.float32),
        atol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
        rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
    )


def test_ragged_moe_gmm_splits_wide_weights(monkeypatch):
    """A weight block over the byte budget is split along F into
    multiples of 128 that divide it."""
    monkeypatch.setattr(gmm_mod, "_W_BLOCK_BYTES", 64 * 128 * 4)
    assert gmm_mod.f_block(64, 384, 4) == 128
    x = rnd(23, (64, 64), jnp.float32)
    w = rnd(24, (3, 64, 384), jnp.float32)
    g = jnp.asarray([16, 0, 32], jnp.int32)
    got = gmm_mod.gmm(x, w, g, block_m=16, interpret=True)[:48]
    np.testing.assert_allclose(got, ref.moe_gmm_ref(x, w, g)[:48],
                               atol=1e-4, rtol=1e-4)


def test_ragged_layout_arithmetic():
    # 6 expected rows a group at decode, 2048 in a prefill chunk
    assert gmm_mod.row_tile(6) == 16
    assert gmm_mod.row_tile(2048) == 512
    # 384 rows over 16 groups may pad each by 15: 624 rows, 39 tiles
    assert gmm_mod.padded_rows(384, 16, 16) == 624
    group, live = gmm_mod.tile_groups(jnp.asarray([0, 32, 16, 0]), 5, 16)
    assert group.tolist() == [1, 1, 2, 3, 3] and live.tolist() == [3]
