"""A run with the timed path broken underneath comes out not correct,
once for each fault the cell can have; an unbroken one comes out correct."""

import numpy as np
import pytest

from conftest import run_tiny


@pytest.mark.parametrize("cell", ["engine.wide", "replica.decode"])
def test_sound_run_is_correct(cell, interpret):
    line = run_tiny(cell)
    assert line["correct"], line["checks"]


def _altered_answer(monkeypatch):
    """One request's latency altered where phase B's answers are
    assembled."""
    from repro.serving.jaxengine import engine

    assemble = engine.assemble_result

    def altered(sched, out):
        res = assemble(sched, out)
        lat = res.latencies_s.copy()
        lat[len(lat) // 2] *= 1.0 + 1e-6
        res.latencies_s = lat
        return res

    monkeypatch.setattr(engine, "assemble_result", altered)


def _half_the_lanes(monkeypatch):
    """Phase B computes half of the batch; the other lanes repeat it."""
    from repro.serving.jaxengine import kernel

    run_group = kernel.run_group

    def half(key, lanes, *grid):
        out = run_group(key, lanes, *grid)
        n = next(iter(out.values())).shape[0]
        keep = np.arange(n) % max(n // 2, 1)
        return {k: v[keep] for k, v in out.items()}

    monkeypatch.setattr(kernel, "run_group", half)


def _state_unchanged(monkeypatch):
    """Phase B hands back its lanes' starting state: nothing resolved."""
    from repro.serving.jaxengine import kernel

    run_group = kernel.run_group

    def unchanged(key, lanes, *grid):
        out = run_group(key, lanes, *grid)
        return {k: (np.zeros_like(v) if k not in ("overflow",) else v)
                for k, v in out.items()}

    monkeypatch.setattr(kernel, "run_group", unchanged)


def _altered_token(monkeypatch):
    """One request's token altered where each decode step produces it."""
    from repro.models.lm import TransformerLM

    decode = TransformerLM.decode_step

    def altered(self, params, tokens, cache, **kw):
        logits, cache = decode(self, params, tokens, cache, **kw)
        return logits.at[1, -1, 7].add(1e3), cache

    monkeypatch.setattr(TransformerLM, "decode_step", altered)


def _cache_unchanged(monkeypatch):
    """Each decode step returns the cache it was given."""
    from repro.models.lm import TransformerLM

    decode = TransformerLM.decode_step

    def unchanged(self, params, tokens, cache, **kw):
        logits, _ = decode(self, params, tokens, cache, **kw)
        return logits, cache

    monkeypatch.setattr(TransformerLM, "decode_step", unchanged)


@pytest.mark.parametrize("cell,fault", [
    ("engine.wide", _altered_answer),
    ("engine.wide", _half_the_lanes),
    ("engine.wide", _state_unchanged),
    ("replica.decode", _altered_token),
    ("replica.decode", _cache_unchanged),
])
def test_fault_is_not_correct(cell, fault, monkeypatch, interpret):
    fault(monkeypatch)
    line = run_tiny(cell)
    assert not line["correct"], line["checks"]
