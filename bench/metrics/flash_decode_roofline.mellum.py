"""``flash_decode``'s share of its roofline in a window/full model: over
every decode step in the window, each layer's least time (the larger of
its operations over peak FLOP/s and its bytes over peak bandwidth, from
``core.moe_flops``, counting the cache positions that hold tokens, at most
the window on a window layer) over the kernel's device time in the
trace."""

from core import kernels, moe_flops, peaks


def read(ctx):
    c, calls = ctx["config"], ctx["readings"].get("calls")
    if not calls:
        return None
    least = moe_flops.least_s(
        [call for B, S, steps in calls for k in range(steps)
         for call in moe_flops.flash_decode(c, B, S + k + 1)],
        peaks.of(ctx["kind"]))
    n = c["num_hidden_layers"] * sum(steps for _, _, steps in calls)
    if not n:
        return None
    t = kernels.time_s(ctx["trace"], "flash_decode", n)
    return None if t is None else 100.0 * least / t
