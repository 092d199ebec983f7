"""Low-overhead span tracer built on the dispatch-tag seam.

One module-global tracer (mirroring :mod:`repro.analysis.contracts`'
module-global counters): :data:`enabled` is the master switch, and the
**disabled path is a single attribute check** — instrumented hot paths
are written as ::

    if _obs.enabled:
        with _obs.span("admission.drain") as sp:
            out = self._drain(now, lanes, select)
            sp.add(placed=len(out))
    ...

so a replay with tracing off allocates nothing and calls nothing (the
``unguarded-obs-in-hot-path`` lint rule enforces the guard).  Tracing
only ever *observes* — ``perf_counter_ns`` timestamps, counter reads —
so traced and untraced replays are bitwise-identical on placements,
retries and evictions (pinned by ``tests/test_obs.py``).

Three event sources feed one bounded ring buffer:

* **spans** — :func:`span` context managers on a thread-local stack;
  each close appends one complete ("X") event with its duration and
  whatever dispatch/compile activity it enclosed.  Each span also
  enters a ``jax.profiler.TraceAnnotation`` of the same name, tagged
  with the stat :data:`SPAN_STAT`, so that while a profiler session
  runs the span lands on the trace's host plane, on the device ops'
  clock (:mod:`repro.obs.xplane` charges device idle time to it);
* **dispatch tags** — :func:`enable` installs a hook into
  :func:`repro.analysis.contracts.record_dispatch`, so every
  self-reported device-program launch (``admission.drain``,
  ``serve.batch``, ...) lands as an instant event *and* is attributed
  to the innermost open span on its thread;
* **compiles** — a lazily registered ``jax.monitoring`` listener (the
  same one-global-listener idiom as ``contracts``: jax has no
  per-listener unregister) turns backend-compile duration events into
  instant events and per-span compile counts.

Export/summary live in :mod:`repro.obs.export`.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import Deque, Dict, List, Optional

__all__ = ["enabled", "enable", "disable", "tracing", "span", "instant",
           "events", "clear", "Span", "DEFAULT_RING", "SPAN_STAT"]

DEFAULT_RING = 65536

# The master switch.  Hot paths read this ONE module attribute and do
# nothing else when it is False.
enabled: bool = False

_ring: Deque[dict] = deque(maxlen=DEFAULT_RING)
_tls = threading.local()
_compile_listener_registered = False
_epoch_ns = time.perf_counter_ns()  # trace-relative timestamp origin

# Stat that marks a span's profiler annotation as a program span, apart
# from the annotations JAX and callers write on the same host plane.
SPAN_STAT = "repro_span"
# ``jax.profiler.TraceAnnotation``, resolved by the first :func:`enable`
# so that importing this module does not import jax.
_annotation = None


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def _now_us() -> float:
    return (time.perf_counter_ns() - _epoch_ns) / 1e3


class Span:
    """One open span: name + start time + absorbed dispatch/compile
    activity.  Appended to the ring as a complete event on exit."""

    __slots__ = ("name", "args", "tid", "t0", "dispatches",
                 "compiles", "compile_us", "_tm")

    def __init__(self, name: str, args: Optional[dict]):
        self.name = name
        self.args = args
        self.tid = threading.get_ident()
        self.t0 = 0.0
        self.dispatches: Optional[Dict[str, int]] = None
        self.compiles = 0
        self.compile_us = 0.0
        self._tm = None

    def add(self, **args) -> "Span":
        """Attach result-side attributes (e.g. ``placed=n``) post-entry."""
        if self.args is None:
            self.args = dict(args)
        else:
            self.args.update(args)
        return self

    def __enter__(self) -> "Span":
        _stack().append(self)
        if _annotation is not None:
            self._tm = _annotation(self.name, **{SPAN_STAT: 1})
            self._tm.__enter__()
        self.t0 = _now_us()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = _now_us()
        if self._tm is not None:
            self._tm.__exit__(None, None, None)
            self._tm = None
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        elif self in st:  # drop inner spans an exception left open
            del st[st.index(self):]
        ev = {"ph": "X", "name": self.name, "ts": self.t0,
              "dur": t1 - self.t0, "tid": self.tid}
        if self.args:
            ev["args"] = self.args
        if self.dispatches:
            ev["dispatches"] = self.dispatches
        if self.compiles:
            ev["compiles"] = self.compiles
            ev["compile_us"] = self.compile_us
        _ring.append(ev)
        return False


class _NoopSpan:
    """Shared do-nothing span for defensive unguarded calls while
    tracing is off."""

    __slots__ = ()

    def add(self, **args) -> "_NoopSpan":
        return self

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NOOP = _NoopSpan()


def span(name: str, **args):
    """Open a span; use as a context manager.  No-op while disabled."""
    if not enabled:
        return _NOOP
    return Span(name, args or None)


def instant(name: str, **args) -> None:
    """Record one instant event.  No-op while disabled."""
    if not enabled:
        return
    ev = {"ph": "i", "name": name, "ts": _now_us(),
          "tid": threading.get_ident(), "s": "t"}
    if args:
        ev["args"] = args
    _ring.append(ev)


# ------------------------------------------------------------------ bridges
def _on_dispatch(tag: str, n: int) -> None:
    """contracts.record_dispatch hook: attribute to the innermost open
    span, or record a loose instant event when no span is open."""
    if not enabled:
        return
    st = _stack()
    if st:
        sp = st[-1]
        if sp.dispatches is None:
            sp.dispatches = {}
        sp.dispatches[tag] = sp.dispatches.get(tag, 0) + n
    else:
        _ring.append({"ph": "i", "name": f"dispatch:{tag}",
                      "ts": _now_us(), "tid": threading.get_ident(),
                      "s": "t"})


def _on_compile_duration(event: str, duration: float, **kw) -> None:
    if not enabled:
        return
    from repro.analysis.contracts import _COMPILE_EVENT
    if event != _COMPILE_EVENT:
        return
    us = duration * 1e6
    st = _stack()
    if st:
        sp = st[-1]
        sp.compiles += 1
        sp.compile_us += us
    else:
        _ring.append({"ph": "i", "name": "jax.compile", "ts": _now_us(),
                      "tid": threading.get_ident(), "s": "t",
                      "args": {"duration_us": us}})


def _ensure_compile_listener() -> None:
    global _compile_listener_registered
    if _compile_listener_registered:
        return
    from jax import monitoring
    monitoring.register_event_duration_secs_listener(_on_compile_duration)
    _compile_listener_registered = True


# ---------------------------------------------------------------- lifecycle
def enable(ring: Optional[int] = None) -> None:
    """Turn tracing on: install the dispatch hook and the compile
    listener, optionally resizing the ring (which clears it)."""
    global enabled, _ring, _annotation
    from jax.profiler import TraceAnnotation

    from repro.analysis import contracts
    _annotation = TraceAnnotation
    if ring is not None and ring != _ring.maxlen:
        _ring = deque(maxlen=int(ring))
    contracts._obs_dispatch_hook = _on_dispatch
    _ensure_compile_listener()
    enabled = True


def disable() -> None:
    """Turn tracing off (the ring's contents stay readable)."""
    global enabled
    from repro.analysis import contracts
    enabled = False
    contracts._obs_dispatch_hook = None


@contextlib.contextmanager
def tracing(ring: Optional[int] = None):
    """Scope-enable tracing; restores the previous on/off state on exit
    (events recorded inside stay in the ring for export)."""
    was = enabled
    enable(ring=ring)
    try:
        yield
    finally:
        if not was:
            disable()


def events() -> List[dict]:
    """Snapshot of the ring, oldest first."""
    return list(_ring)


def clear() -> None:
    _ring.clear()
