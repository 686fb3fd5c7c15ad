"""The price model's KV arithmetic on caches that are not one dense stack:
a window layer keeps at most its window, a hybrid keeps KV only in its
attention blocks; a model of dense full attention prices as before."""

import pytest

from repro.cluster.catalog import default_catalog
from repro.configs import get_config
from repro.serving.latency import LatencyModel

CAT = default_catalog()


def _lm(arch):
    return LatencyModel.for_model(get_config(arch),
                                  CAT.instance_type("g5.48xlarge"))


def test_dense_model_prices_as_before():
    """command-r-35b: every one of 40 layers holds 8 KV heads of 128 for
    every token, whatever the context; concurrency is free HBM over a
    4096-token sequence's KV, as the model priced it before windows."""
    lm = _lm("command-r-35b")
    per_token = 2 * 40 * 8 * 128 * 2
    assert lm.kv_bytes_per_token() == per_token
    assert lm.kv_bytes_per_token(4096) == per_token
    assert lm.max_concurrency() == max(
        1, int(lm.free_kv_hbm_bytes() / (per_token * 4096)))
    assert lm.max_concurrency(512) == max(
        1, int(lm.free_kv_hbm_bytes() / (per_token * 512)))


def test_all_window_model_reserves_its_window():
    """h2o-danube3-4b: every layer windowed at 4096, so an 8192-token
    sequence holds 4096 positions, as the model priced it before."""
    lm = _lm("h2o-danube3-4b")
    layer = 2 * 8 * 120 * 2
    assert lm.kv_bytes_per_token() == 24 * layer
    assert lm.kv_bytes_per_token(8192) == pytest.approx(24 * layer / 2)
    assert lm.max_concurrency(8192) == max(
        1, int(lm.free_kv_hbm_bytes() / (24 * layer * 4096)))


@pytest.mark.parametrize("ctx,layers", [
    (None, 28), (1024, 28), (2560, 7 + 21 * 1024 / 2560),
])
def test_window_layers_count_up_to_their_window(ctx, layers):
    """mellum2-12b: 21 window layers (1024 positions) and 7 full ones."""
    lm = _lm("mellum2-12b")
    assert lm.kv_bytes_per_token(ctx) == pytest.approx(
        layers * 2 * 4 * 128 * 2)


def test_hybrid_keeps_kv_in_its_attention_blocks():
    """zamba2-7b: 13 shared-attention blocks among 81 layers."""
    cfg = get_config("zamba2-7b")
    lm = _lm("zamba2-7b")
    assert lm.kv_bytes_per_token() == (
        cfg.hybrid_blocks * 2 * cfg.num_kv_heads * cfg.resolved_head_dim * 2)
