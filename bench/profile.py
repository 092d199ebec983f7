"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device numbers.

Device planes are those named ``/device:TPU:<n>``.  On each, the line
``XLA Ops`` holds one event per operation and ``XLA Modules`` one per
program run.  Busy time is the union of the operation intervals (the
module intervals where a plane has no op line), averaged over devices.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

__all__ = ["Trace", "find", "from_profile_data", "idle_pct", "load",
           "union_ns"]

Interval = Tuple[float, float]   # (start_ns, end_ns)


def union_ns(iv: List[Interval]) -> List[Interval]:
    """Merge overlapping intervals; sorted, disjoint output."""
    out: List[Interval] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


class Trace:
    """What the reduction keeps of one trace.

    ``devices`` maps a device plane's name to ``{"ops": [(name, start,
    dur)], "modules": [(name, start, dur)]}`` with times in ns.
    """

    def __init__(self, devices: Dict[str, dict]):
        self.devices = devices

    def _busy(self, dev: dict) -> List[Interval]:
        rows = dev["ops"] or dev["modules"]
        return union_ns([(s, s + d) for _, s, d in rows])

    def busy_s(self) -> Optional[float]:
        """Seconds in which some operation ran, averaged over devices."""
        if not self.devices:
            return None
        tot = [sum(e - s for s, e in self._busy(d))
               for d in self.devices.values()]
        return sum(tot) / len(tot) / 1e9

    def module_s(self, match) -> Tuple[float, int]:
        """Device seconds and runs of the programs whose module name
        ``match(name)`` accepts, averaged over devices."""
        if not self.devices:
            return 0.0, 0
        s = n = 0
        for d in self.devices.values():
            for name, _, dur in d["modules"]:
                if match(name):
                    s += dur
                    n += 1
        k = len(self.devices)
        return s / k / 1e9, n // k

    def op_totals(self, top: int = 10) -> List[list]:
        """The operations that took most device time, in seconds."""
        acc: Dict[str, float] = defaultdict(float)
        for d in self.devices.values():
            for name, _, dur in d["ops"] or d["modules"]:
                acc[name] += dur / 1e9
        k = max(len(self.devices), 1)
        rows = sorted(((n, v / k) for n, v in acc.items()),
                      key=lambda r: -r[1])
        return [[n, v] for n, v in rows[:top]]

    def idle_gaps(self, top: int = 10) -> List[list]:
        """The longest idle gaps of the first device, in seconds, each
        named by the programs that ran before and after it: the host
        work between them."""
        if not self.devices:
            return []
        dev = self.devices[sorted(self.devices)[0]]
        busy = self._busy(dev)
        mods = sorted((s, s + d, n) for n, s, d in dev["modules"])

        def around(t):
            prev = nxt = "start"
            for s, e, n in mods:
                if e <= t:
                    prev = n
                elif s >= t:
                    nxt = n
                    break
            return prev, nxt
        gaps = []
        for (s0, e0), (s1, _) in zip(busy, busy[1:]):
            gaps.append((s1 - e0, e0))
        gaps.sort(key=lambda g: -g[0])
        out = []
        for g, t in gaps[:top]:
            prev, nxt = around(t)
            out.append([f"host between {prev} and {nxt}", g / 1e9])
        return out


def idle_pct(ctx) -> Optional[float]:
    """Share of the traced window in which no operation ran on the
    device, in %: the per-layer reader ``device_idle_pct.<cell kind>``."""
    tr = ctx.get("trace")
    busy = tr.busy_s() if tr is not None else None
    if busy is None or not ctx.get("window_s"):
        return None
    return 100.0 * (1.0 - busy / ctx["window_s"])


def from_profile_data(pd) -> Trace:
    devices = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        rows = {"ops": [], "modules": []}
        for line in plane.lines:
            key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
            if key is None:
                continue
            rows[key].extend((e.name, float(e.start_ns), float(e.duration_ns))
                             for e in line.events)
        devices[plane.name] = rows
    return Trace(devices)


def find(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            return from_profile_data(
                ProfileData.from_serialized_xspace(f.read()))
    return from_profile_data(ProfileData.from_file(path))
