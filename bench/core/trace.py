"""Reduce a profiler trace of the window to what the metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` wrote and
keeps, for each TPU device, its operation events and its program
(module) events, and on the host the harness's own annotations (the
``window`` annotation bounds what is measured).  ``Trace`` is plain data
with a JSON form, so the reductions can be checked on a small recorded
trace without a chip.

* busy time: the union of the device's operation intervals inside the
  window, averaged over the devices used;
* idle share: 1 - busy / window;
* a program's time: the summed durations of its executions;
* a kernel's time: the summed durations of its operation events;
* breakdown: the device operations that took most time, and the longest
  idle gaps named by the innermost host annotation around each.

An operation's event is named by its HLO instruction
(``%fusion.12 = bf16[...] fusion(...), ...``); it is kept as the
instruction's name (``fusion.12``) with the first ``LABEL`` characters of
the whole text beside it, for the breakdown.
"""

from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional, Tuple

# device planes and their lines in a TPU trace
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW = "window"
LABEL = 160

Event = Tuple[str, float, float]          # name, start ns, duration ns


def _merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


class Trace:
    def __init__(self, window: Tuple[float, float],
                 devices: List[Dict[str, List[Event]]],
                 host: List[Event]) -> None:
        self.window = window
        # per device: "ops" and "modules" events, and "labels": each op
        # name's HLO text, cut to LABEL characters
        self.devices = devices
        self.host = host

    # -- JSON form ------------------------------------------------------
    def to_json(self) -> str:
        return json.dumps({"window": self.window, "devices": self.devices,
                           "host": self.host})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        devices = [{"ops": [tuple(e) for e in dev["ops"]],
                    "modules": [tuple(e) for e in dev["modules"]],
                    "labels": dev.get("labels", {})}
                   for dev in d["devices"]]
        return cls(tuple(d["window"]), devices,
                   [tuple(e) for e in d["host"]])

    # -- reductions -----------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _busy(self, dev) -> List[Tuple[float, float]]:
        w0, w1 = self.window
        return _merge([(max(s, w0), min(s + d, w1)) for _, s, d in dev["ops"]
                       if s + d > w0 and s < w1])

    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the devices."""
        per = [sum(b - a for a, b in self._busy(d)) for d in self.devices]
        return sum(per) / len(per) / 1e9

    def idle_share(self) -> float:
        """Percent of the window with no operation on the device."""
        return 100.0 * (1.0 - self.busy_s() / self.window_s)

    def ops(self, match) -> List[Event]:
        """Operation events of the first device whose name ``match``
        accepts."""
        return [e for e in self.devices[0]["ops"] if match(e[0])]

    def program_s(self, prefix: str) -> float:
        """Seconds of the first device's program executions whose module
        name starts with ``prefix``."""
        return sum(d for n, _, d in self.devices[0]["modules"]
                   if n.startswith(prefix)) / 1e9

    def breakdown(self, top: int = 10) -> Dict[str, List]:
        dev = self.devices[0]
        w0, w1 = self.window
        by_op: Dict[str, float] = {}
        for n, s, d in dev["ops"]:
            if s + d > w0 and s < w1:
                by_op[n] = by_op.get(n, 0.0) + d
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        labels = dev.get("labels", {})
        busy = self._busy(dev)
        edges = [w0] + [x for ab in busy for x in ab] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return {
            "device_ops": [[labels.get(n, n), d / 1e9] for n, d in ops],
            "idle_gaps": [[self.host_at((a + b) / 2), (b - a) / 1e9]
                          for a, b in gaps[:top]],
        }

    def host_at(self, t: float) -> str:
        """The innermost harness annotation open at time ``t``."""
        inner: Optional[Event] = None
        for e in self.host:
            n, s, d = e
            if n != WINDOW and s <= t <= s + d and (
                    inner is None or d < inner[2]):
                inner = e
        return inner[0] if inner else "host"


def load(trace_dir: str, n_devices: int, annotations=()) -> Trace:
    """The newest trace under ``trace_dir``, reduced for ``n_devices``
    TPU devices; the host keeps the annotations named in
    ``annotations`` and the window's."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    data = ProfileData.from_file(path)
    keep = set(annotations) | {WINDOW}
    devices: Dict[int, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            idx = int(plane.name[len(DEVICE_PREFIX):])
            if idx >= n_devices:
                continue
            dev = devices.setdefault(idx, {"ops": [], "modules": [],
                                           "labels": {}})
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    dev["modules"].extend((e.name, e.start_ns, e.duration_ns)
                                          for e in line.events)
                elif line.name == OPS_LINE:
                    labels = dev["labels"]
                    for e in line.events:
                        text = e.name
                        name = text.split(" = ", 1)[0].lstrip("%")
                        if name not in labels:
                            labels[name] = text[:LABEL]
                        dev["ops"].append((name, e.start_ns, e.duration_ns))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events if e.name in keep)
    windows = [e for e in host if e[0] == WINDOW]
    if not windows or len(devices) < n_devices:
        raise RuntimeError(f"trace {path} holds no window annotation or "
                           f"fewer than {n_devices} TPU devices")
    _, s, d = windows[0]
    return Trace((s, s + d), [devices[i] for i in sorted(devices)], host)
