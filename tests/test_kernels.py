"""Pallas kernels vs. jnp oracles (interpret=True on CPU), sweeping
shapes/dtypes per the deliverable."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.flash_decode import flash_decode_bhd
from repro.kernels.moe_gmm import moe_gmm_ecf
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


@pytest.mark.parametrize("case", DECODE_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_matches_ref(case, dtype):
    B, H, Kv, S, D, n_valid = case
    q = rnd(7, (B, H, D), dtype)
    k = rnd(8, (B, Kv, S, D), dtype)
    v = rnd(9, (B, Kv, S, D), dtype)
    valid = (jnp.arange(S)[None, :] < n_valid).astype(jnp.int8)
    valid = jnp.broadcast_to(valid, (B, S))
    got = flash_decode_bhd(q, k, v, valid, block_kv=128, interpret=True)
    want = ref.flash_decode_ref(q, k, v, valid)
    np.testing.assert_allclose(
        got.astype(np.float32), want.astype(np.float32),
        atol=TOLS[dtype], rtol=TOLS[dtype],
    )


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
    (8, 96, 200, 64),       # non-aligned dims exercise padding
    (2, 256, 512, 512),
]


@pytest.mark.parametrize("case", GMM_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm_matches_ref(case, dtype):
    E, C, D, F = case
    x = rnd(15, (E, C, D), dtype)
    w = rnd(16, (E, D, F), dtype)
    got = moe_gmm_ecf(x, w, block_c=64, block_d=64, block_f=64,
                      interpret=True)
    want = ref.moe_gmm_ref(x, w)
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
