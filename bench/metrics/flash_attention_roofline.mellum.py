"""``flash_attention``'s share of its roofline in a window/full model:
over every prefill in the window, each layer's least time (the larger of
its operations over peak FLOP/s and its bytes over peak bandwidth, from
``core.moe_flops``, with a window layer's pairs cut to its window) over
the kernel's device time in the trace."""

from core import kernels, moe_flops, peaks


def read(ctx):
    c, calls = ctx["config"], ctx["readings"].get("calls")
    if not calls:
        return None
    least = moe_flops.least_s(
        [call for B, S, _ in calls
         for call in moe_flops.flash_attention(c, B, S)],
        peaks.of(ctx["kind"]))
    n = c["num_hidden_layers"] * len(calls)
    t = kernels.time_s(ctx["trace"], "flash_attention", n)
    return None if t is None else 100.0 * least / t
