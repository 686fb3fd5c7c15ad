"""Rate and latency arithmetic of the window on a fake clock, with and
without a stall inside it."""

import types

import jax.numpy as jnp
import pytest

from drivers import matrix, replica


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _replica(monkeypatch, stall_at=None, stall=0.0):
    clock = Clock()
    monkeypatch.setattr(replica.time, "perf_counter", clock)
    d = object.__new__(replica.Driver)
    d.B, d.S, d.out = 2, 4, 5
    d.params, d.cache = None, None
    steps = [0]

    def prefill(params, prompts, cache):
        clock.t += 0.5
        return jnp.zeros((2, 1), jnp.int32), cache

    def decode(params, tok, cache):
        steps[0] += 1
        clock.t += 0.01 + (stall if steps[0] == stall_at else 0.0)
        return jnp.zeros((2, 1), jnp.int32), cache

    d._prefill, d._decode = prefill, decode
    d._prompts = lambda r: jnp.zeros((2, 4), jnp.int32)
    return d


def test_replica_window_counts_every_emitted_token(monkeypatch):
    d = _replica(monkeypatch)
    out = d.window(1.0)
    # a round is a 0.5 s prefill and 4 steps of 10 ms; the window ends
    # after the first call past 1 s: a whole round, then the next prefill
    assert d.readings["window_s"] == pytest.approx(1.04)
    assert d.readings["tokens"] == 2 * (5 + 1)
    assert out["metrics"]["tokens_per_s"] == pytest.approx(12 / 1.04)
    assert out["metrics"]["itl_ms_mean"] == pytest.approx(10.0)
    assert out["attempted"] == 4


def test_replica_stall_lowers_the_rate_and_shows_in_itl(monkeypatch):
    calm = _replica(monkeypatch).window(1.0)["metrics"]
    stalled = _replica(monkeypatch, stall_at=2, stall=0.3).window(1.0)
    m = stalled["metrics"]
    assert m["tokens_per_s"] < calm["tokens_per_s"]
    # 4 decode steps in the window, one of them 300 ms late
    assert m["itl_ms_mean"] == pytest.approx(10.0 + 300.0 / 4)


def _matrix(monkeypatch, stall_eval=None):
    clock = Clock()
    monkeypatch.setattr(matrix.time, "perf_counter", clock)
    d = object.__new__(matrix.Driver)
    d.phase_a_s = 0.0
    d.fallback_counter = "jax_numpy_fallback"
    evals = [0]

    def evaluate():
        evals[0] += 1
        clock.t += 2.0 + (5.0 if evals[0] == stall_eval else 0.0)
        return types.SimpleNamespace(cells=[None] * 4, metrics=None)

    d._evaluate = evaluate
    d.clock = clock
    return d


def test_matrix_rate_is_over_whole_evaluations(monkeypatch):
    d = _matrix(monkeypatch)
    out = d.window(5.0)
    # whole evaluations until 5 s have passed: three of 2 s
    assert d.readings["evaluations"] == 3
    assert out["metrics"]["cells_per_s"] == pytest.approx(12 / 6.0)
    stalled = _matrix(monkeypatch, stall_eval=2).window(5.0)
    assert stalled["metrics"]["cells_per_s"] == pytest.approx(8 / 9.0)


def test_matrix_counts_numpy_fallbacks_as_failed(monkeypatch):
    d = _matrix(monkeypatch)
    rep = types.SimpleNamespace(cells=[None] * 4, metrics={"counters": {
        "jax_numpy_fallback{reason=overflow}": 1,
        "jax_numpy_fallback{reason=token}": 2, "other": 5}})

    def evaluate():
        d.clock.t += 1.0
        return rep

    d._evaluate = evaluate
    assert d.window(0.0)["failed"] == 3
    assert d.readings["evaluations"] == 1
