"""Device idle time named by host span, from a JAX profiler trace.

While a profiler session runs, every :mod:`repro.obs` span is also a
``TraceAnnotation`` on the trace's host plane (``/host:CPU``), marked
with the stat :data:`repro.obs.trace.SPAN_STAT` and on the same clock
as the device planes.  :func:`summarize_xplane` reads such a trace
(``*.xplane.pb``, or gzipped) and answers what the host was doing
while the device idled:

* the idle intervals are the gaps of the first device's busy time —
  the union of its ``XLA Ops`` events, or of ``XLA Modules`` where the
  device has no op line — inside the trace's extent;
* each idle instant is charged to the innermost program span open on
  the host at that moment (the latest-opened), or to
  :data:`OUTSIDE` when none is open.

The interval arithmetic (:func:`union`, :func:`gaps`,
:func:`charge_idle`, :func:`span_rows`) works on plain ``(start, end)``
lists and is independent of the trace format.  Annotations written by
callers or by JAX itself (``bench.replay``, ``PjitFunction(...)``) carry
no marker and are not program spans.

CLI: ``python -m repro.obs summarize --xplane <file>``.
"""

from __future__ import annotations

import gzip
import heapq
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from repro.obs.trace import SPAN_STAT

__all__ = ["OUTSIDE", "union", "gaps", "charge_idle", "span_rows",
           "load", "read", "summarize_xplane"]

OUTSIDE = "(outside any span)"

Interval = Tuple[float, float]            # (start, end)
NamedInterval = Tuple[str, float, float]  # (name, start, end)


def union(iv: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals; sorted, disjoint output."""
    out: List[Interval] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of ``[lo, hi]`` that no interval of ``busy`` covers."""
    out: List[Interval] = []
    t = lo
    for s, e in union(busy):
        if e <= t:
            continue
        if s >= hi:
            break
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def charge_idle(idle: Sequence[Interval],
                spans: Sequence[NamedInterval]) -> Dict[str, float]:
    """Charge every idle instant to the innermost span open then.

    The innermost of the open spans is the one opened last (of two
    opened at once, the one that closes first).  Time no span covers is
    charged to :data:`OUTSIDE`.  Returns ``{name: time}``; the values
    sum to the idle intervals' total length.
    """
    # Sweep over every boundary; at one instant, closes go before opens.
    pts: List[Tuple[float, int, int]] = []
    for k, (_, s, e) in enumerate(spans):
        if e > s:
            pts.append((s, 1, k))
            pts.append((e, 0, k))
    for s, e in union(idle):
        pts.append((s, 2, -1))   # idle starts (after the opens at s)
        pts.append((e, -1, -1))  # idle ends (before the closes at e)
    pts.sort()
    out: Dict[str, float] = defaultdict(float)
    heap: List[Tuple[float, float, int]] = []  # (-start, end, k)
    closed = set()
    in_idle = False
    t_prev = None
    for t, kind, k in pts:
        if in_idle and t > t_prev:
            while heap and heap[0][2] in closed:
                heapq.heappop(heap)
            name = spans[heap[0][2]][0] if heap else OUTSIDE
            out[name] += t - t_prev
        t_prev = t
        if kind == 1:
            heapq.heappush(heap, (-spans[k][1], spans[k][2], k))
        elif kind == 0:
            closed.add(k)
        else:
            in_idle = kind == 2
    return dict(out)


def span_rows(spans: Sequence[Tuple[str, float, float, object]]
              ) -> Dict[str, dict]:
    """Per span name: count, total time and self time (total less the
    time of the program spans directly inside it on the same thread).
    ``spans`` holds ``(name, start, end, thread)``."""
    rows: Dict[str, dict] = defaultdict(
        lambda: {"n": 0, "total": 0.0, "self": 0.0})
    by_thread: Dict[object, list] = defaultdict(list)
    for name, s, e, tid in spans:
        by_thread[tid].append((s, -e, name))
    for evs in by_thread.values():
        evs.sort()
        stack: List[list] = []   # open spans: [end, name, own, children]
        for s, neg_e, name in evs:
            while stack and stack[-1][0] <= s:
                _, n, own, kids = stack.pop()
                rows[n]["self"] += own - kids
            dur = -neg_e - s
            rows[name]["n"] += 1
            rows[name]["total"] += dur
            if stack:
                stack[-1][3] += dur
            stack.append([-neg_e, name, dur, 0.0])
        for _, n, own, kids in stack:
            rows[n]["self"] += own - kids
    return dict(rows)


# ------------------------------------------------------------------ reading
def load(path: str):
    """``jax.profiler.ProfileData`` of a trace file (``.gz`` or not)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _is_span(ev) -> bool:
    return any(k == SPAN_STAT for k, _ in ev.stats)


def read(pd) -> dict:
    """What :func:`summarize_xplane` needs of a trace: the program spans
    on the host plane ``(name, start_ns, end_ns, line)``, the first
    device's name and busy intervals, and the trace's extent."""
    spans: list = []
    lo, hi = float("inf"), float("-inf")
    devices = {}
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    s, e = float(ev.start_ns), float(ev.end_ns)
                    lo, hi = min(lo, s), max(hi, e)
                    if _is_span(ev):
                        spans.append((ev.name, s, e, line.name))
        elif plane.name.startswith("/device:"):
            rows = {line.name: [(float(ev.start_ns), float(ev.end_ns))
                                for ev in line.events]
                    for line in plane.lines
                    if line.name in ("XLA Ops", "XLA Modules")}
            busy = rows.get("XLA Ops") or rows.get("XLA Modules")
            if busy:
                devices[plane.name] = busy
    device, busy = None, []
    if devices:
        device = sorted(devices)[0]
        busy = union(devices[device])
        if busy:
            lo, hi = min(lo, busy[0][0]), max(hi, busy[-1][1])
    if lo > hi:
        lo = hi = 0.0
    return {"spans": spans, "device": device, "busy": busy,
            "extent": (lo, hi)}


def summarize_xplane(path: str) -> str:
    """Per program span: count, total ms, self ms and the device-idle ms
    charged to it; then the idle time outside any span and the totals."""
    tr = read(load(path))
    lo, hi = tr["extent"]
    rows = span_rows(tr["spans"])
    charged: Dict[str, float] = {}
    idle_ns: Optional[float] = None
    if tr["device"] is not None:
        idle = gaps(tr["busy"], lo, hi)
        idle_ns = sum(e - s for s, e in idle)
        charged = charge_idle(idle, [sp[:3] for sp in tr["spans"]])

    def idle_col(name):
        return (f"{charged.get(name, 0.0) / 1e6:>11.3f}"
                if idle_ns is not None else f"{'-':>11}")

    head = (f"{'span':<28} {'count':>7} {'total_ms':>11} {'self_ms':>11} "
            f"{'idle_ms':>11}")
    lines = [head, "-" * len(head)]
    for name in sorted(rows, key=lambda n: -rows[n]["total"]):
        r = rows[name]
        lines.append(f"{name:<28} {r['n']:>7} {r['total'] / 1e6:>11.3f} "
                     f"{r['self'] / 1e6:>11.3f} {idle_col(name)}")
    if not rows:
        lines.append("(no program spans on the host plane)")
    lines.append(f"{OUTSIDE:<28} {'':>7} {'':>11} {'':>11} "
                 f"{idle_col(OUTSIDE)}")
    lines.append("")
    window = (hi - lo) / 1e6
    if idle_ns is None:
        lines.append(f"no device plane with XLA Ops or XLA Modules; "
                     f"trace extent {window:.3f} ms")
    else:
        busy = sum(e - s for s, e in tr["busy"]) / 1e6
        lines.append(
            f"{tr['device']}: window {window:.3f} ms, busy {busy:.3f} ms, "
            f"idle {idle_ns / 1e6:.3f} ms "
            f"({100.0 * idle_ns / max(hi - lo, 1e-9):.3f}%), charged "
            f"{sum(charged.values()) / 1e6:.3f} ms")
    return "\n".join(lines)
