"""``flash_decode``'s share of its roofline: over every decode step in the
window, the least time the chip could take for each call (the larger of
its operations over peak FLOP/s and its bytes over peak bandwidth, from
``core.flops``, counting only the cache positions that hold tokens) over
the kernel's device time in the trace."""

from core import flops, kernels, peaks



def read(ctx):
    c, calls = ctx["config"], ctx["readings"].get("calls")
    if not calls:
        return None
    p = peaks.of(ctx["kind"])
    least, n = 0.0, 0
    for B, S, steps in calls:
        for k in range(steps):
            f, b = flops.flash_decode(B, c["num_heads"], c["num_kv_heads"],
                                      S + k + 1, c["head_dim"])
            least += c["num_layers"] * max(f / p["bf16_flops"],
                                           b / p["hbm_bytes_per_s"])
        n += c["num_layers"] * steps
    if not n:
        return None
    t = kernels.time_s(ctx["trace"], "flash_decode", n)
    return None if t is None else 100.0 * least / t
