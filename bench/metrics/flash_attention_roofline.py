"""``flash_attention``'s share of its roofline: over every prefill in the
window, the least time the chip could take for each call (the larger of
its operations over peak FLOP/s and its bytes over peak bandwidth, from
``core.flops``) over the kernel's device time in the trace."""

from core import flops, kernels, peaks



def read(ctx):
    c, calls = ctx["config"], ctx["readings"].get("calls")
    if not calls:
        return None
    p = peaks.of(ctx["kind"])
    least = 0.0
    for B, S, _ in calls:
        f, b = flops.flash_attention(B, c["num_heads"], c["num_kv_heads"],
                                     S, c["head_dim"])
        least += c["num_layers"] * max(f / p["bf16_flops"],
                                       b / p["hbm_bytes_per_s"])
    n = c["num_layers"] * len(calls)
    t = kernels.time_s(ctx["trace"], "flash_attention", n)
    return None if t is None else 100.0 * least / t
