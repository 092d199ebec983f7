"""Plain reference of a cluster replay: KS+ tasks packed by memory envelope.

The semantics the benchmark holds the program's ``ClusterSim`` to, written
from the paper's deployment model and nothing of the program:

* Nodes admit a queued task when its allocation envelope, sampled on
  ``GRID`` points over its estimated runtime, fits under the node's
  residual envelope at those absolute times (tolerance ``TOL`` GB).  The
  residual is the capacity minus every resident task's envelope, where a
  resident counts from its admission time for its true runtime.
* Admission is greedy: the first queued task (queue order) that fits some
  node goes to the first node (node order) it fits; repeat until nothing
  fits.  Placing only shrinks residuals, so a task that does not fit
  stays unfit until the next event.
* A placed task runs its hidden trace.  It is killed at the first sample
  whose use exceeds its allocation (OOM): that attempt's whole allocation
  up to and including the sample is wasted, the plan is re-timed or its
  last peak bumped (KS+ section II-C) and the task queues again at the
  back.  Attempt 1 is probed in float32 and later attempts in float64
  with a 1e-12 GB tolerance, as the configuration states.  A task whose
  trace exceeds the largest node, or that runs out of attempts, is
  unschedulable, and so are all its not-yet-released descendants.
* A task that finishes wastes its allocation minus its use, integrated
  over its samples, and releases its children (in submission order).
* Events at the same time are processed in the order they were pushed,
  with a drain after each.

``dtype`` sets the precision of every computation; float32 gives the
control (the reference one precision below the configuration's float64).
"""

from __future__ import annotations

import heapq
import itertools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

GRID = 64          # samples on the admission horizon
TOL = 1e-9         # admission tolerance, GB
PROBE_TOL = 1e-12  # OOM tolerance of the float64 retry probes, GB


@dataclass
class Task:
    """One task as the workflow engine submits it."""

    jid: int
    mem: np.ndarray          # hidden trace, GB per sample
    dt: float
    starts: np.ndarray       # allocation plan: segment start times, s
    peaks: np.ndarray        # allocation plan: segment peaks, GB
    est_runtime: float       # runtime estimate for the admission horizon, s
    parents: Tuple[int, ...] = ()


@dataclass
class Outcome:
    placements: List[Tuple[float, int, int]]
    retries: int
    unschedulable: int
    finished: int
    makespan: float
    wastage_gbs: float
    peak_queue: int          # longest queue left after a drain
    roots_waited: int        # tasks released at t=0 but placed later


def _alloc(starts, peaks, rel):
    """Step envelopes ``(L, K)`` at times ``rel`` ``(L, M)``: the peak of
    the last segment whose start is at or before the time."""
    a = np.broadcast_to(peaks[:, :1], rel.shape)
    for k in range(1, starts.shape[1]):
        a = np.where(starts[:, k:k + 1] <= rel, peaks[:, k:k + 1], a)
    return a


def _ksplus_retry(starts, peaks, t_fail, bump):
    """KS+ section II-C on one plan: a failure inside segment j < last
    starts segment j+1 at the failure time and scales the later starts by
    the same factor; a failure in the last segment raises its peak by
    ``bump``.  Starts and peaks stay non-decreasing."""
    K = starts.size
    j = max(int(np.sum(starts <= t_fail)) - 1, 0)
    if j >= K - 1:
        pk = peaks.copy()
        pk[-1] = pk[-1] * (1 + bump)
        return starts.copy(), np.maximum.accumulate(pk)
    nxt = starts[j + 1]
    factor = t_fail / nxt if nxt > 0 else 0.0 * t_fail
    st = starts.copy()
    st[j + 1:] = starts[j + 1:] * factor
    st[j + 1] = t_fail
    st = np.maximum.accumulate(np.maximum(st, 0))
    st[0] = 0
    return st, peaks.copy()


def replay(tasks: Sequence[Task], caps: Sequence[float], *, bump: float,
           max_attempts: int, dtype=np.float64) -> Outcome:
    f = np.dtype(dtype).type
    B = len(tasks)
    K = max(len(t.starts) for t in tasks)
    caps = np.asarray(caps, dtype)
    N = caps.size
    cap_max = caps.max()
    index = {t.jid: i for i, t in enumerate(tasks)}
    starts = np.full((B, K), 1e30, dtype)
    peaks = np.zeros((B, K), dtype)
    for i, t in enumerate(tasks):
        n = len(t.starts)
        starts[i, :n] = t.starts
        peaks[i, :n] = t.peaks
        peaks[i, n:] = t.peaks[-1]
    mems = [np.asarray(t.mem, dtype) for t in tasks]
    dts = np.asarray([t.dt for t in tasks], dtype)
    runtime = np.asarray([len(m) for m in mems], dtype) * dts
    est = np.asarray([t.est_runtime for t in tasks], dtype)
    grid = np.linspace(f(0), est, GRID, axis=1).astype(dtype)   # (B, GRID)
    need = _alloc(starts, peaks, grid)
    peak_demand = np.asarray([m.max() for m in mems], dtype)
    sum_mem = np.asarray([m.sum(dtype=dtype) for m in mems], dtype)

    children: List[List[int]] = [[] for _ in range(B)]
    pending = np.zeros(B, np.int64)
    for i, t in enumerate(tasks):
        for p in dict.fromkeys(t.parents):
            children[index[p]].append(i)
            pending[i] += 1
    dead = np.zeros(B, bool)

    def first_violation(i, attempt1):
        m = mems[i]
        if attempt1 and dtype == np.float64:
            m32 = m.astype(np.float32)
            t = np.arange(m.size, dtype=np.float32) * np.float32(dts[i])
            a = _alloc(starts[i:i + 1].astype(np.float32),
                       peaks[i:i + 1].astype(np.float32), t[None])[0]
            bad = m32 > a
        else:
            t = np.arange(m.size, dtype=dtype) * dts[i]
            a = _alloc(starts[i:i + 1], peaks[i:i + 1], t[None])[0]
            bad = m > a + f(PROBE_TOL)
        return int(np.argmax(bad)) if bad.any() else -1

    def alloc_sum(i, upto):
        t = np.arange(upto, dtype=dtype) * dts[i]
        return _alloc(starts[i:i + 1], peaks[i:i + 1], t[None])[0].sum(
            dtype=dtype)

    viol = np.asarray([first_violation(i, True) for i in range(B)])
    attempts = np.zeros(B, np.int64)
    admit_t = np.zeros(B, dtype)
    residents: List[List[int]] = [[] for _ in range(N)]
    queue: List[int] = []
    events: List[tuple] = []
    seq = itertools.count()
    placements: List[Tuple[float, int, int]] = []
    wasted = f(0)
    retries = unschedulable = finished = 0
    makespan = f(0)
    peak_queue = 0

    def fits(n, q_idx):
        """Does each queued task in ``q_idx`` fit node ``n`` now?"""
        run = residents[n]
        q_idx = np.asarray(q_idx, np.int64)
        if not run:
            return np.all(need[q_idx] <= caps[n] + f(TOL), axis=1)
        r = np.asarray(run)
        tabs = now + grid[q_idx]                          # (Q, GRID)
        rel = tabs.reshape(1, -1) - admit_t[r][:, None]   # (R, Q*GRID)
        a = _alloc(starts[r], peaks[r], np.maximum(rel, f(0)))
        active = (rel >= 0) & (rel < runtime[r][:, None] + f(TOL))
        used = np.where(active, a, f(0)).sum(axis=0, dtype=dtype)
        resid = (caps[n] - used).reshape(q_idx.size, GRID)
        return np.all(need[q_idx] <= resid + f(TOL), axis=1)

    def resid_at(n, q_idx, g):
        """Residual of node ``n`` at grid point ``g`` of each queued task:
        a necessary condition, since every grid point must fit."""
        run = residents[n]
        if not run:
            return np.full(len(q_idx), caps[n], dtype)
        r = np.asarray(run)
        tabs = now + grid[q_idx, g]
        rel = tabs[None, :] - admit_t[r][:, None]
        a = _alloc(starts[r], peaks[r], np.maximum(rel, f(0)))
        active = (rel >= 0) & (rel < runtime[r][:, None] + f(TOL))
        return caps[n] - np.where(active, a, f(0)).sum(axis=0, dtype=dtype)

    def place(i, n):
        residents[n].append(i)
        admit_t[i] = now
        placements.append((float(now), n, tasks[i].jid))
        if viol[i] < 0:
            heapq.heappush(events, (now + runtime[i], next(seq), "done", n, i))
        else:
            heapq.heappush(events, (now + f(viol[i]) * dts[i], next(seq),
                                    "oom", n, i))

    def drain():
        nonlocal queue, peak_queue
        if queue:
            park = need[queue].max(axis=1) > cap_max + f(TOL)
            if park.any():   # never placeable: left out of every drain
                queue = [i for i, p in zip(queue, park) if not p]
        if queue:
            q = np.asarray(queue)
            F = np.zeros((N, q.size), bool)
            for n in range(N):
                cand = need[q, 0] <= resid_at(n, q, 0) + f(TOL)
                cand &= need[q, -1] <= resid_at(n, q, -1) + f(TOL)
                if cand.any():
                    F[n, cand] = fits(n, q[cand])
            alive = np.ones(q.size, bool)
            while True:
                anyfit = F.any(axis=0) & alive
                if not anyfit.any():
                    break
                col = int(np.argmax(anyfit))
                n = int(np.argmax(F[:, col]))
                alive[col] = False
                place(int(q[col]), n)
                again = np.nonzero(alive & F[n])[0]
                if again.size:
                    F[n, again] = fits(n, q[again])
            queue = [int(i) for i in q[alive]]
        peak_queue = max(peak_queue, len(queue))

    queue = [i for i in range(B) if pending[i] == 0]
    roots = set(queue)
    now = f(0)
    drain()
    while events:
        now, _, kind, n, i = heapq.heappop(events)
        residents[n].remove(i)
        if kind == "done":
            wasted += (alloc_sum(i, mems[i].size) - sum_mem[i]) * dts[i]
            makespan = max(makespan, now)
            finished += 1
            for c in children[i]:
                pending[c] -= 1
                if pending[c] == 0 and not dead[c]:
                    queue.append(c)
        else:
            v = int(viol[i])
            wasted += alloc_sum(i, v + 1) * dts[i]
            retries += 1
            if attempts[i] + 1 < max_attempts and peak_demand[i] <= cap_max:
                attempts[i] += 1
                starts[i], peaks[i] = _ksplus_retry(
                    starts[i], peaks[i], f(v) * dts[i], f(bump))
                need[i] = _alloc(starts[i:i + 1], peaks[i:i + 1],
                                 grid[i:i + 1])[0]
                viol[i] = first_violation(i, False)
                queue.append(i)
            else:
                attempts[i] += 1
                unschedulable += 1
                stack = list(children[i])
                while stack:
                    c = stack.pop()
                    if not dead[c]:
                        dead[c] = True
                        unschedulable += 1
                        stack.extend(children[c])
        drain()
    first: Dict[int, float] = {}
    for t, _, j in placements:
        first.setdefault(j, t)
    waited = sum(1 for j, t in first.items() if t > 0 and index[j] in roots)
    return Outcome(placements, retries, unschedulable, finished,
                   float(makespan), float(wasted), peak_queue, waited)
