"""Device time of a Pallas kernel in a trace.

A Pallas kernel's calls appear on the device as operations named after
the kernel (``flash_decode.5``).  Its time counts only when the trace
holds as many calls as the window made: a kernel taken off the path, or a
trace that lost events, reads nothing rather than a wrong share.
"""

from __future__ import annotations

from typing import Optional


def time_s(trace, kernel: str, calls: int) -> Optional[float]:
    events = trace.ops(
        lambda name: name == kernel or name.startswith(kernel + "."))
    if len(events) != calls:
        return None
    return sum(d for _, _, d in events) / 1e9
