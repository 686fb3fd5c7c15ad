"""Operations and bytes the replica step needs, from its shapes.

Counts are what the algorithm needs, not what a kernel happens to do: a
causal attention needs only the pairs at or below the diagonal, a decode
step reads only the cache positions that hold tokens.  A multiply-add is
two operations; bytes are of bfloat16 (2 bytes) unless said otherwise.
"""

from __future__ import annotations

from typing import Dict, Tuple

BF16 = 2


def flash_attention(B: int, H: int, Kv: int, S: int, D: int
                    ) -> Tuple[float, float]:
    """Causal self-attention over S positions: QK^T and PV on the
    S(S+1)/2 pairs of each of the B*H heads; q, k, v read, out written."""
    pairs = S * (S + 1) / 2
    flops = 4.0 * B * H * pairs * D
    nbytes = BF16 * (2 * B * H * S * D + 2 * B * Kv * S * D)
    return flops, nbytes


def flash_decode(B: int, H: int, Kv: int, L: int, D: int
                 ) -> Tuple[float, float]:
    """One query per head against L cached positions: k and v of the L
    positions read, q read and out written."""
    flops = 4.0 * B * H * L * D
    nbytes = BF16 * (2 * B * Kv * L * D + 2 * B * H * D)
    return flops, nbytes


def layer_weights(c: Dict) -> int:
    """Weights of one decoder layer's matmuls."""
    d, h, kv, hd, f = (c["d_model"], c["num_heads"], c["num_kv_heads"],
                       c["head_dim"], c["d_ff"])
    return d * (h + 2 * kv) * hd + h * hd * d + 3 * d * f


def prefill(c: Dict, B: int, S: int) -> float:
    """Model operations of a prefill of B prompts of S tokens: every
    layer's matmuls on B*S tokens, causal attention, and the logits of
    the last position."""
    L = c["num_layers"]
    att, _ = flash_attention(B, c["num_heads"], c["num_kv_heads"], S,
                             c["head_dim"])
    return (2.0 * L * layer_weights(c) * B * S + L * att
            + 2.0 * B * c["d_model"] * c["vocab_size"])


def decode(c: Dict, B: int, ctx: int) -> float:
    """Model operations of one decode step of B sequences whose new token
    attends to ``ctx`` positions (itself included)."""
    L = c["num_layers"]
    att, _ = flash_decode(B, c["num_heads"], c["num_kv_heads"], ctx,
                          c["head_dim"])
    return (2.0 * L * layer_weights(c) * B + L * att
            + 2.0 * B * c["d_model"] * c["vocab_size"])


def rounds(c: Dict, calls) -> float:
    """Model operations of the window's rounds: ``calls`` holds, per
    round, (batch, prompt length, decode steps run)."""
    total = 0.0
    for B, S, steps in calls:
        total += prefill(c, B, S)
        total += sum(decode(c, B, S + k + 1) for k in range(steps))
    return total
