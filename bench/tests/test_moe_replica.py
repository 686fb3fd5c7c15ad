"""The ``moe_replica`` driver and its reference at a tiny Mellum-shaped
size on the CPU: a sound run of ``mellum.code`` is correct, each planted
fault is not, the float8 control fails the limit; the configuration file
reads as the registered model; ``core.moe_flops`` against hand counts."""

import dataclasses
import functools
import importlib

import jax
import jax.numpy as jnp
import pytest

CELL = "mellum.code"
SLIDING, FULL = "sliding_attention", "full_attention"

# two periods of the pattern at small widths; the window is shorter than
# the prompt, so the ring wraps; YaRN's ramp lies inside the 64-wide head
TINY_MOE = {
    "num_hidden_layers": 8, "hidden_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 64, "moe_intermediate_size": 128,
    "vocab_size": 512, "sliding_window": 64, "num_experts": 4,
    "router_experts": 16, "num_experts_per_tok": 4,
    "layer_types": ([SLIDING] * 3 + [FULL]) * 2,
    "rope_parameters": {
        FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 4,
               "original_max_position_embeddings": 64, "beta_fast": 32,
               "beta_slow": 1, "attention_factor": 1.2},
        SLIDING: {"rope_type": "default", "rope_theta": 500000},
    },
}
TINY_ROUNDS = {"batch": 4, "prompt": 128, "output": 64, "cache_slots": 256,
               "check_requests": 4}


@pytest.fixture
def tiny(monkeypatch, interpret):
    """The cell at the tiny size, its kernels interpreted."""
    import run

    from repro.kernels import ops

    monkeypatch.setattr(ops, "moe_gmm", functools.partial(
        ops.moe_gmm, interpret=True))
    load = run.load_cell

    def tiny_cell(name):
        c = load(name)
        c["config"] = dict(c["config"], **TINY_MOE)
        c["traffic"] = dict(c["traffic"], **TINY_ROUNDS)
        return c

    monkeypatch.setattr(run, "load_cell", tiny_cell)
    return tiny_cell


def _run_cell(seed):
    """A whole run of the cell, past the harness's look for a chip."""
    import types

    import run

    args = types.SimpleNamespace(workload=CELL, seed=seed, seconds=0.5,
                                 trace=0)
    return run.run(args, jax.devices()[:1], run.load_cell(CELL))


def test_sound_run_is_correct(tiny):
    line = _run_cell(2**31 + 17)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}


def _with_cfg(monkeypatch, **change):
    """The program built with its configuration changed."""
    from drivers import moe_replica

    build = moe_replica.model_config
    monkeypatch.setattr(moe_replica, "model_config",
                        lambda c: dataclasses.replace(build(c), **change))


def _offset_off_by_one(monkeypatch):
    """The layer takes its experts for experts 1-4 of the router's."""
    _with_cfg(monkeypatch, expert_offset=1)


def _no_yarn(monkeypatch):
    """Full layers rotate without YaRN."""
    _with_cfg(monkeypatch, full_attn_yarn=None)


def _no_window(monkeypatch):
    """Window layers attend to, and keep, every position."""
    _with_cfg(monkeypatch, sliding_window=10**6)


def _capacity(monkeypatch):
    """The serving layer keeps at most ``capacity_factor`` times an
    expert's even share of the assignments, in token order, and drops
    the rest."""
    from repro.models import moe

    def dropping(p, cfg, x, *, impl="einsum"):
        B, S, d = x.shape
        N, k, H = B * S, cfg.experts_per_token, cfg.held_experts
        xf = x.reshape(N, d)
        logits = xf.astype(jnp.float32) @ p["router"].astype(jnp.float32)
        weights, idx = moe.route_topk(logits, k)
        onehot = jax.nn.one_hot(idx - cfg.expert_offset, H)  # (N, k, H)
        flat = onehot.reshape(N * k, H)
        pos = (jnp.cumsum(flat, 0) * flat).sum(-1).reshape(N, k) - 1
        keep = pos < moe._capacity(cfg, N)
        gate = (onehot * (weights * keep)[..., None]).sum(1)    # (N, H)
        dt = x.dtype
        h = jnp.einsum("nd,edf->enf", xf, p["wi"].astype(dt))
        g = jnp.einsum("nd,edf->enf", xf, p["wg"].astype(dt))
        y = jnp.einsum("enf,efd->end", jax.nn.silu(g) * h,
                       p["wo"].astype(dt))
        y = jnp.einsum("end,ne->nd", y.astype(jnp.float32), gate)
        load = onehot.sum((0, 1)).astype(jnp.int32)
        return y.astype(dt).reshape(B, S, d), load

    monkeypatch.setattr(moe, "moe_serve", dropping)


@pytest.mark.parametrize("fault", [_offset_off_by_one, _no_yarn,
                                   _no_window, _capacity])
def test_fault_is_not_correct(fault, monkeypatch, tiny):
    fault(monkeypatch)
    line = _run_cell(2**31 + 17)
    assert not line["correct"], line["checks"]


def test_control_fails_the_limit(tiny):
    import run

    c = run.load_cell(CELL)
    mod = importlib.import_module(f"drivers.{c['config']['kind']}")
    d = mod.Driver(c["config"], c["traffic"], 2**31 + 3, c["limits"])
    d.setup()
    d.window(0.5)
    d.free()
    assert all(v["value"] <= v["limit"] for v in d.check().values())
    control = d.control()
    assert any(v > c["limits"][k] for k, v in control.items()), control
    # the counter counts every held assignment of the window
    load = d.readings["moe_load"]
    assert load.shape == (8, 4) and load.sum() > 0


def test_window_runs_whole_rounds(tiny):
    """A round started inside the window runs to its end: every request
    gets all its output tokens."""
    import run

    c = run.load_cell(CELL)
    mod = importlib.import_module(f"drivers.{c['config']['kind']}")
    d = mod.Driver(c["config"], c["traffic"], 2**31 + 5, c["limits"])
    d.setup()
    result = d.window(1e-6)
    out = TINY_ROUNDS["output"]
    assert d.readings["calls"] == [(4, 128, out - 1)]
    assert d.readings["tokens"] == 4 * out == result["attempted"] * out
    assert len(d.rounds[0]["served"]) == out


def test_configuration_is_the_registered_model():
    """The driver's ModelConfig is ``get_config("mellum2-12b")`` but for
    the held experts."""
    import run

    from drivers import moe_replica
    from repro.configs import get_config

    cfg = moe_replica.model_config(run.load_cell(CELL)["config"])
    assert (cfg.experts_held, cfg.expert_offset) == (16, 0)
    assert dataclasses.replace(cfg, experts_held=None) == get_config(
        "mellum2-12b")


# ---------------------------------------------------------------------------
# operation counts
# ---------------------------------------------------------------------------

SMALL = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 2,
         "moe_intermediate_size": 3, "vocab_size": 10, "sliding_window": 2,
         "num_experts": 2, "router_experts": 8, "num_experts_per_tok": 2,
         "layer_types": [SLIDING, FULL]}


def test_window_limits_attention_pairs():
    from core import moe_flops

    assert moe_flops.attention_pairs(3, None) == 6
    # positions 0..4 under a window of 2 see 1, 2, 2, 2, 2
    assert moe_flops.attention_pairs(5, 2) == 9
    assert moe_flops.attention_pairs(2, 4) == 3


def test_prefill_and_decode_counts():
    from core import moe_flops

    # dense weights a layer: q 8*4*2, k and v 8*2*2 each, o 4*2*8,
    # router 8*8: 64+32+32+64+64 = 256; an expert 3*8*3 = 72; held rows of
    # N tokens N*2*2/8 = N/2; head 2*B*8*10
    B, S = 3, 5
    window = 2 * 256 * B * S + 4 * B * 4 * 9 * 2 + 2 * 72 * (B * S / 2)
    full = 2 * 256 * B * S + 4 * B * 4 * 15 * 2 + 2 * 72 * (B * S / 2)
    assert moe_flops.prefill(SMALL, B, S) == pytest.approx(
        window + full + 2 * B * 8 * 10)
    # a decode step at ctx 6: the window layer sees 2, the full one 6
    dec = (2 * (2 * 256 * B + 2 * 72 * B / 2) + 4 * B * 4 * (2 + 6) * 2
           + 2 * B * 8 * 10)
    assert moe_flops.decode(SMALL, B, 6) == pytest.approx(dec)


# ---------------------------------------------------------------------------
# readers
# ---------------------------------------------------------------------------


def _read(name, ctx):
    import run

    return run.read_metric(name, ctx)


def test_mfu_and_imbalance_readers():
    import numpy as np

    from core import moe_flops

    calls = [(2, 4, 1)]
    ctx = {"config": SMALL, "readings": {"calls": calls}, "kind": "TPU v5e",
           "window_s": 2.0}
    assert _read("mfu.mellum", ctx) == pytest.approx(
        100 * moe_flops.rounds(SMALL, calls) / 2.0 / 197e12)
    # layer 0 even, layer 1 one expert with 3 of 4
    ctx["readings"]["moe_load"] = np.array([[2, 2], [3, 1]])
    assert _read("expert_imbalance.mellum", ctx) == pytest.approx(
        (1.0 + 1.5) / 2)
    # the parent's program has no counter: nothing to read
    del ctx["readings"]["moe_load"]
    assert _read("expert_imbalance.mellum", ctx) is None



def test_flash_roofline_readers():
    from core import moe_flops
    from core.trace import Trace

    p = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    # SMALL: 4 heads, 2 KV heads of 2, window 2; a prefill of 3 x 5: the
    # window layer on 9 pairs a sequence, the full one on 15; q and out
    # 2*3*4*5*2 elements, k and v 2*3*2*5*2, 2 bytes each
    nbytes = 2 * (2 * 3 * 4 * 5 * 2 + 2 * 3 * 2 * 5 * 2)
    assert moe_flops.flash_attention(SMALL, 3, 5) == [
        (4 * 3 * 4 * 9 * 2, nbytes), (4 * 3 * 4 * 15 * 2, nbytes)]
    # a decode step at ctx 6 reads 2 positions on the window layer, 6 on
    # the full one: k and v 2*3*2*L*2 elements, q and out 2*3*4*2
    assert moe_flops.flash_decode(SMALL, 3, 6) == [
        (4 * 3 * 4 * L * 2, 2 * (2 * 3 * 2 * L * 2 + 2 * 3 * 4 * 2))
        for L in (2, 6)]
    assert moe_flops.least_s([(197e12, 1.0), (1.0, 819e9)], p) == 2.0

    calls = [(3, 5, 2)]           # a prefill, then 2 decode steps
    fa = [("flash_attention.1", 10.0 * i, 4.0) for i in range(2)]
    fd = [("flash_decode.2", 100.0 + 10 * i, 1.0) for i in range(4)]
    ctx = {"config": SMALL, "readings": {"calls": calls},
           "kind": "TPU v5e",
           "trace": Trace((0.0, 1e3), [{"ops": fa + fd, "modules": []}],
                          [])}
    assert _read("flash_attention_roofline.mellum", ctx) == pytest.approx(
        100 * moe_flops.least_s(moe_flops.flash_attention(SMALL, 3, 5), p)
        / 8e-9)
    assert _read("flash_decode_roofline.mellum", ctx) == pytest.approx(
        100 * moe_flops.least_s(moe_flops.flash_decode(SMALL, 3, 6)
                                + moe_flops.flash_decode(SMALL, 3, 7), p)
        / 4e-9)
    # a call missing from the trace reads nothing
    ctx["trace"].devices[0]["ops"] = fa + fd[1:]
    assert _read("flash_decode_roofline.mellum", ctx) is None
