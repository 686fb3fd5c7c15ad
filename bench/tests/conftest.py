"""The benchmark's own tests, run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import functools  # noqa: E402
import types  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

# tiny shapes of each kind, run on the CPU with the Pallas kernels
# interpreted
# (the embedding's spread is raised so that logits spread as widely as at
# d_model 8192 with the configuration's 0.02: 0.02 * sqrt(8192 / 256))
TINY_MODEL = {"num_layers": 2, "d_model": 256, "num_heads": 4,
              "num_kv_heads": 2, "head_dim": 64, "d_ff": 512,
              "vocab_size": 512, "embed_std": 0.1131}
TINY_ROUNDS = {"batch": 4, "prompt": 128, "output": 64, "cache_slots": 256,
               "check_requests": 4}
TINY_MATRIX = {"seeds": [11, 12], "hours": 0.25}


@pytest.fixture
def interpret(monkeypatch):
    """The kernels in Pallas interpret mode, as the models call them."""
    from repro.kernels import ops

    for name in ("flash_attention", "flash_decode"):
        monkeypatch.setattr(ops, name, functools.partial(
            getattr(ops, name), interpret=True))


@pytest.fixture
def cpu_peaks(monkeypatch):
    from core import peaks

    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.PEAKS["TPU v5e"])


def tiny_cell(name: str) -> dict:
    import run

    c = run.load_cell(name)
    if c["config"]["kind"] == "matrix":
        c["traffic"] = dict(c["traffic"], **TINY_MATRIX)
    else:
        c["config"] = dict(c["config"], **TINY_MODEL)
        c["traffic"] = dict(c["traffic"], **TINY_ROUNDS)
    return c


def run_tiny(name: str, seconds: float = 0.5, trace: int = 0,
             seed: int = 2**31 + 17) -> dict:
    """A whole run of cell ``name`` at tiny shapes on the CPU, past the
    harness's look for a chip."""
    import run

    args = types.SimpleNamespace(workload=name, seed=seed, seconds=seconds,
                                 trace=trace)
    return run.run(args, jax.devices()[:1], tiny_cell(name))
