"""The whole MoE replica step's share of the chip's bf16 peak: the model
operations of every prefill and decode step in the window for the held
share (``core.moe_flops``) over the window's length."""

from core import moe_flops, peaks


def read(ctx):
    calls = ctx["readings"].get("calls")
    if not calls:
        return None
    ops = moe_flops.rounds(ctx["config"], calls)
    peak = peaks.of(ctx["kind"])["bf16_flops"]
    return 100.0 * ops / ctx["window_s"] / peak
