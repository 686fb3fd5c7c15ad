"""Micro-benchmark the Pallas kernels into per-model step-time tables.

For each model config the profiler times the kernels its architecture
actually runs per serving step — prefill (flash attention / selective
scan / MoE grouped matmul at config shapes) and per-token decode (flash
decode over a populated KV cache; a one-step scan for state-space
archs) — and converts the measurements into the two numbers the roofline
latency model consumes:

* ``mfu_prefill`` — achieved prefill FLOP/s over the target instance's
  peak (``accel_count × peak_bf16_tflops``),
* ``mbu_decode``  — achieved decode HBM bytes/s over the instance's peak
  bandwidth (``accel_count × hbm_bytes_per_s``).

On a TPU backend with ``interpret=False`` these are real utilization
measurements.  On CPU (interpret mode — the kernel body runs in Python)
the pipeline is identical but the efficiencies are orders of magnitude
below hardware truth; such tables validate the profile→latency plumbing
end-to-end and are tagged ``mode: interpret`` so nobody mistakes them
for silicon numbers.
"""

from __future__ import annotations

import time
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.cluster.catalog import InstanceType
from repro.configs import get_config
from repro.kernels import ops
from repro.models.config import ModelConfig
from repro.profiles.schema import ProfileEntry, ProfileTable

__all__ = ["profile_model", "profile_models"]

# keep interpret-mode scan chunks bounded: the recurrence is sequential
# in time, so one chunk is the natural (and repeated) unit of work
_SCAN_CHUNK = 64
# MoE prefill capacity per expert (tokens routed to one expert)
_MOE_CAPACITY = 128


def _time_call(
    fn: Callable[[], jax.Array], repeats: int
) -> float:
    """Best-of-``repeats`` wall seconds, after one untimed warmup call
    (tracing/compilation must not be billed as step time)."""
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _rnd(seed: int, shape: Tuple[int, ...], dtype) -> jax.Array:
    return jax.random.normal(
        jax.random.PRNGKey(seed), shape, jnp.float32
    ).astype(dtype)


def _prefill_cases(
    cfg: ModelConfig, tokens: int, batch: int, interpret: bool
) -> List[Tuple[Callable[[], jax.Array], float]]:
    """(thunk, flops) per kernel the arch runs during prefill."""
    cases: List[Tuple[Callable[[], jax.Array], float]] = []
    if cfg.num_heads:
        B, S = batch, tokens
        H, Kv, D = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
        q = _rnd(1, (B, S, H, D), jnp.bfloat16)
        k = _rnd(2, (B, S, Kv, D), jnp.bfloat16)
        v = _rnd(3, (B, S, Kv, D), jnp.bfloat16)
        # QK^T + PV are 2·S²·D MACs each per head; causal halves the
        # live blocks
        flops = 4.0 * B * H * S * S * D * 0.5
        cases.append((
            lambda: ops.flash_attention(
                q, k, v, causal=True, interpret=interpret
            ),
            flops,
        ))
    if cfg.family in ("ssm", "hybrid"):
        B, Q = batch, min(tokens, _SCAN_CHUNK)
        C, N = cfg.d_inner, cfg.ssm_state
        a = jax.nn.sigmoid(_rnd(4, (B, Q, C, N), jnp.float32))
        b = _rnd(5, (B, Q, C, N), jnp.float32) * 0.1
        h0 = jnp.zeros((B, C, N), jnp.float32)
        # h = a·h + b: one mul + one add per (C, N) element per step
        flops = 2.0 * B * Q * C * N
        cases.append((
            lambda: ops.selective_scan(a, b, h0, interpret=interpret),
            flops,
        ))
    if cfg.is_moe:
        E, C = cfg.num_experts, _MOE_CAPACITY
        D, F = cfg.d_model, cfg.expert_d_ff
        x = _rnd(6, (E * C, D), jnp.bfloat16)
        w = _rnd(7, (E, D, F), jnp.bfloat16)
        sizes = jnp.full((E,), C, jnp.int32)
        flops = 2.0 * E * C * D * F
        cases.append((
            lambda: ops.moe_gmm(x, w, sizes, block_m=C,
                                interpret=interpret),
            flops,
        ))
    if not cases:
        raise ValueError(
            f"model family {cfg.family!r} maps to no profiled kernel"
        )
    return cases


def _decode_cases(
    cfg: ModelConfig, cache_tokens: int, batch: int, interpret: bool
) -> List[Tuple[Callable[[], jax.Array], float]]:
    """(thunk, bytes-moved) per kernel one decode step runs."""
    cases: List[Tuple[Callable[[], jax.Array], float]] = []
    if cfg.num_heads:
        B, S = batch, cache_tokens
        H, Kv, D = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
        q = _rnd(8, (B, 1, H, D), jnp.bfloat16)
        # one layer's head-major cache, as a one-layer stack
        kc = _rnd(9, (1, B, Kv, S, D), jnp.bfloat16)
        vc = _rnd(10, (1, B, Kv, S, D), jnp.bfloat16)
        valid = jnp.ones((B, S), jnp.int8)
        # decode attention streams the whole K and V cache once
        nbytes = 2.0 * B * Kv * S * D * kc.dtype.itemsize
        cases.append((
            lambda: ops.flash_decode(
                q, kc, vc, jnp.int32(0), kv_valid=valid, interpret=interpret
            ),
            nbytes,
        ))
    if cfg.family in ("ssm", "hybrid"):
        B = batch
        C, N = cfg.d_inner, cfg.ssm_state
        a = jax.nn.sigmoid(_rnd(11, (B, 1, C, N), jnp.float32))
        b = _rnd(12, (B, 1, C, N), jnp.float32) * 0.1
        h0 = _rnd(13, (B, C, N), jnp.float32)
        # read a, b, h; write h' — all fp32
        nbytes = 4.0 * B * C * N * 4
        cases.append((
            lambda: ops.selective_scan(a, b, h0, interpret=interpret),
            nbytes,
        ))
    if not cases:
        raise ValueError(
            f"model family {cfg.family!r} maps to no profiled kernel"
        )
    return cases


def profile_model(
    model_id: str,
    itype: InstanceType,
    *,
    prefill_tokens: int = 256,
    cache_tokens: int = 512,
    batch: int = 1,
    decode_steps: int = 4,
    repeats: int = 2,
    interpret: Optional[bool] = None,
) -> ProfileEntry:
    """Measure one (model × instance-accelerator) step-time row."""
    cfg = get_config(model_id)
    if interpret is None:
        # compiled kernels only where they can run
        interpret = jax.default_backend() != "tpu"

    # attention kernels measure the full requested prompt; scan kernels
    # always measure one chunk (the unit the model repeats across a
    # prompt — see schema.ProfileEntry.prefill_tokens).  For attention-
    # free archs the chunk therefore IS the measured prompt length.
    measured_tokens = (
        prefill_tokens if cfg.num_heads
        else min(prefill_tokens, _SCAN_CHUNK)
    )

    prefill_wall = 0.0
    prefill_flops = 0.0
    for fn, flops in _prefill_cases(cfg, prefill_tokens, batch, interpret):
        prefill_wall += _time_call(fn, repeats)
        prefill_flops += flops

    decode_wall = 0.0
    decode_bytes = 0.0
    for fn, nbytes in _decode_cases(cfg, cache_tokens, batch, interpret):
        decode_wall += _time_call(fn, max(repeats, decode_steps))
        decode_bytes += nbytes

    peak_flops = itype.accel_count * itype.peak_bf16_tflops * 1e12
    peak_bytes = itype.accel_count * itype.hbm_bytes_per_s
    return ProfileEntry(
        model=model_id,
        accelerator=itype.accelerator,
        backend=jax.default_backend(),
        mode="interpret" if interpret else "compiled",
        jax_version=jax.__version__,
        prefill_tokens=measured_tokens,
        prefill_flops=prefill_flops,
        prefill_wall_s=prefill_wall,
        decode_cache_tokens=cache_tokens,
        decode_steps=decode_steps,
        decode_bytes=decode_bytes,
        decode_wall_s=decode_wall,
        mfu_prefill=(prefill_flops / prefill_wall) / peak_flops,
        mbu_decode=(decode_bytes / decode_wall) / peak_bytes,
    )


def profile_models(
    model_ids,
    itype: InstanceType,
    *,
    table: Optional[ProfileTable] = None,
    **kwargs,
) -> ProfileTable:
    """Profile several models into one table (merging into ``table``)."""
    out = table if table is not None else ProfileTable()
    out.jax_version = jax.__version__
    for model_id in model_ids:
        entry = profile_model(model_id, itype, **kwargs)
        out.add(entry)
        out.backend = entry.backend
        out.mode = entry.mode
    return out
