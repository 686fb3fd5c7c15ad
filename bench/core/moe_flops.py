"""Operations of the ``moe_replica`` step for the chip's share,
from its shapes.

As in ``core.flops``, counts are what the algorithm needs: attention only
on the causal pairs a layer's window admits, the expert layer only on the
assignments routed to the held experts, taken as the expected held
assignments ``N·k·held/E`` for ``N`` tokens, ``k`` experts a token and
``held`` of the router's ``E``.  A multiply-add is two operations.

``flash_attention`` and ``flash_decode`` give each layer's kernel call
as (operations, bytes), for the kernels' rooflines: the bytes as
``core.flops`` counts them, over the positions a layer keeps.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from core import flops


def _dims(c: Dict) -> Tuple[int, ...]:
    return (c["num_hidden_layers"], c["hidden_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["moe_intermediate_size"], c["vocab_size"])


def _full(c: Dict):
    """Each layer's kind: True where it attends to the whole cache."""
    return [t == "full_attention" for t in c["layer_types"]]


def attention_pairs(S: int, window) -> float:
    """Causal pairs of S positions, each query seeing at most ``window``
    positions (itself included; None: all before it)."""
    if window is None or S <= window:
        return S * (S + 1) / 2
    return window * (window + 1) / 2 + (S - window) * window


def held_rows(c: Dict, tokens: int) -> float:
    """Expected assignments of ``tokens`` tokens to the held experts."""
    return (tokens * c["num_experts_per_tok"] * c["num_experts"]
            / c["router_experts"])


def _layer_dense(c: Dict) -> int:
    """Weights of one layer's matmuls that every token uses: attention
    projections and the router."""
    _, d, h, kv, hd, _, _ = _dims(c)
    return d * (h + 2 * kv) * hd + h * hd * d + d * c["router_experts"]


def _expert(c: Dict) -> int:
    """Weights of one expert (SwiGLU: wi, wg, wo)."""
    _, d, _, _, _, f, _ = _dims(c)
    return 3 * d * f


def prefill(c: Dict, B: int, S: int) -> float:
    """Model operations of a prefill of B prompts of S tokens."""
    L, d, h, _, hd, _, V = _dims(c)
    ops = 2.0 * B * d * V
    for full in _full(c):
        pairs = attention_pairs(S, None if full else c["sliding_window"])
        ops += (2.0 * _layer_dense(c) * B * S + 4.0 * B * h * pairs * hd
                + 2.0 * _expert(c) * held_rows(c, B * S))
    return ops


def decode(c: Dict, B: int, ctx: int) -> float:
    """Model operations of one decode step of B sequences whose new token
    attends to ``ctx`` positions (itself included), within the window on
    sliding layers."""
    L, d, h, _, hd, _, V = _dims(c)
    ops = 2.0 * B * d * V
    for full in _full(c):
        seen = ctx if full else min(ctx, c["sliding_window"])
        ops += (2.0 * _layer_dense(c) * B + 4.0 * B * h * seen * hd
                + 2.0 * _expert(c) * held_rows(c, B))
    return ops


def rounds(c: Dict, calls) -> float:
    """Model operations of the window's rounds: ``calls`` holds, per
    round, (batch, prompt length, decode steps run)."""
    total = 0.0
    for B, S, steps in calls:
        total += prefill(c, B, S)
        total += sum(decode(c, B, S + k + 1) for k in range(steps))
    return total


def flash_attention(c: Dict, B: int, S: int) -> List[Tuple[float, float]]:
    """(operations, bytes) of each layer's ``flash_attention`` call in a
    prefill of B prompts of S tokens: QK^T and PV on the causal pairs the
    layer's window admits; q, k and v read and out written once."""
    _, _, h, kv, hd, _, _ = _dims(c)
    _, nbytes = flops.flash_attention(B, h, kv, S, hd)
    return [(4.0 * B * h * attention_pairs(
        S, None if full else c["sliding_window"]) * hd, nbytes)
        for full in _full(c)]


def flash_decode(c: Dict, B: int, ctx: int) -> List[Tuple[float, float]]:
    """(operations, bytes) of each layer's ``flash_decode`` call in a
    decode step whose new token attends to ``ctx`` positions: k and v of
    the positions the layer keeps (on window layers at most the window)
    read, q read and out written."""
    _, _, h, kv, hd, _, _ = _dims(c)
    return [flops.flash_decode(
        B, h, kv, ctx if full else min(ctx, c["sliding_window"]), hd)
        for full in _full(c)]


def least_s(calls: List[Tuple[float, float]], peak: Dict) -> float:
    """Least time of kernel calls given as (operations, bytes): each the
    larger of its operations over peak FLOP/s and its bytes over peak
    bandwidth."""
    return sum(max(f / peak["bf16_flops"], b / peak["hbm_bytes_per_s"])
               for f, b in calls)
