"""Plain reference of a cluster replay under node churn.

The cohort reference (``replay.py``: envelope admission, greedy first-fit
drains, OOM kills and KS+ re-plans, DAG release and doom) with node
membership events, written from the semantics the deployment states and
nothing of the program:

* **Leave** (a node is reclaimed): its residents are killed in admission
  order.  Each wastes its allocation over the whole samples it ran,
  ``min(floor((t - t_admit) / dt + 1e-9), length)``; its pending done or
  OOM event goes stale; its attempt count rises by one, against the same
  ``max_attempts`` as OOM kills.  One out of attempts is unschedulable,
  and so are its unreleased descendants; the others go to the front of
  the queue, in admission order.  The plan is kept: a kill by the
  cluster says nothing about the task's memory.
* **Join** (a node comes back): it goes last in first-fit order, and
  every parked task goes back to the front of the queue.
* **Park**: a drain first sets aside every queued task whose admission
  need peaks above the largest up node (all of them while no node is up)
  until the next join; the wait counts into ``starvation_s``, and a task
  still parked at the end is charged up to the last event.
* Membership events are pushed before any done or OOM event, so at equal
  times they come first; each event, stale ones aside, is followed by a
  drain.

With no events this is ``replay.replay`` exactly.  ``dtype`` sets the
precision of every computation, as there.
"""

from __future__ import annotations

import heapq
import itertools
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench.reference.replay import (GRID, PROBE_TOL, TOL, Outcome, Task,
                                    _alloc, _ksplus_retry)

__all__ = ["ChurnOutcome", "Task", "replay"]

# One membership event: (t, "leave" | "join", node id, capacity in GB,
# which a leave ignores).
Event = Tuple[float, str, int, float]


@dataclass
class ChurnOutcome(Outcome):
    evictions: int = 0       # residents killed by leaves
    starved: int = 0         # tasks neither finished nor unschedulable
    starvation_s: float = 0.0  # total time tasks spent parked


def replay(tasks: Sequence[Task], caps: Sequence[float],
           events: Sequence[Event] = (), *, bump: float, max_attempts: int,
           dtype=np.float64) -> ChurnOutcome:
    """Replay ``tasks`` on nodes ``0 .. len(caps) - 1`` of capacities
    ``caps`` under the membership ``events``, in time order."""
    f = np.dtype(dtype).type
    B = len(tasks)
    K = max(len(t.starts) for t in tasks)
    caps = np.asarray(caps, dtype)
    cap_max = caps.max()     # the submitted fleet's largest node
    cap_of: Dict[int, object] = {n: caps[n] for n in range(caps.size)}
    up: List[int] = list(range(caps.size))    # first-fit order
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
    epoch = np.zeros(B, np.int64)     # a kill makes older events stale
    admit_t = np.zeros(B, dtype)
    residents: Dict[int, List[int]] = {n: [] for n in up}
    queue: List[int] = []
    parked: List[int] = []
    park_t: Dict[int, object] = {}
    heap: List[tuple] = []
    seq = itertools.count()
    placements: List[Tuple[float, int, int]] = []
    wasted = f(0)
    retries = unschedulable = finished = evictions = 0
    starvation_s = 0.0
    makespan = f(0)
    peak_queue = 0

    for t, kind, n, cap in events:
        heapq.heappush(heap, (f(t), next(seq), kind, int(n), f(cap), -1))

    def fits(n, q_idx):
        """Does each queued task in ``q_idx`` fit node ``n`` now?"""
        run = residents[n]
        q_idx = np.asarray(q_idx, np.int64)
        if not run:
            return np.all(need[q_idx] <= cap_of[n] + f(TOL), axis=1)
        r = np.asarray(run)
        tabs = now + grid[q_idx]                          # (Q, GRID)
        rel = tabs.reshape(1, -1) - admit_t[r][:, None]   # (R, Q*GRID)
        a = _alloc(starts[r], peaks[r], np.maximum(rel, f(0)))
        active = (rel >= 0) & (rel < runtime[r][:, None] + f(TOL))
        used = np.where(active, a, f(0)).sum(axis=0, dtype=dtype)
        resid = (cap_of[n] - used).reshape(q_idx.size, GRID)
        return np.all(need[q_idx] <= resid + f(TOL), axis=1)

    def resid_at(n, q_idx, g):
        """Residual of node ``n`` at grid point ``g`` of each queued task:
        a necessary condition, since every grid point must fit."""
        run = residents[n]
        if not run:
            return np.full(len(q_idx), cap_of[n], dtype)
        r = np.asarray(run)
        tabs = now + grid[q_idx, g]
        rel = tabs[None, :] - admit_t[r][:, None]
        a = _alloc(starts[r], peaks[r], np.maximum(rel, f(0)))
        active = (rel >= 0) & (rel < runtime[r][:, None] + f(TOL))
        return cap_of[n] - np.where(active, a, f(0)).sum(axis=0, dtype=dtype)

    def place(i, n):
        residents[n].append(i)
        admit_t[i] = now
        placements.append((float(now), n, tasks[i].jid))
        if viol[i] < 0:
            t_end, kind = now + runtime[i], "done"
        else:
            t_end, kind = now + f(viol[i]) * dts[i], "oom"
        heapq.heappush(heap, (t_end, next(seq), kind, n, i, int(epoch[i])))

    def drain():
        nonlocal queue, peak_queue
        if queue:
            cap_hi = max((cap_of[n] for n in up), default=f(0))
            park = need[queue].max(axis=1) > cap_hi + f(TOL)
            if park.any():   # no up node could ever hold them
                for i, p in zip(queue, park):
                    if p:
                        parked.append(i)
                        park_t[i] = now
                queue = [i for i, p in zip(queue, park) if not p]
        if queue and up:
            q = np.asarray(queue)
            F = np.zeros((len(up), q.size), bool)
            for row, n in enumerate(up):
                cand = need[q, 0] <= resid_at(n, q, 0) + f(TOL)
                cand &= need[q, -1] <= resid_at(n, q, -1) + f(TOL)
                if cand.any():
                    F[row, cand] = fits(n, q[cand])
            alive = np.ones(q.size, bool)
            while True:
                anyfit = F.any(axis=0) & alive
                if not anyfit.any():
                    break
                col = int(np.argmax(anyfit))
                row = int(np.argmax(F[:, col]))
                alive[col] = False
                place(int(q[col]), up[row])
                again = np.nonzero(alive & F[row])[0]
                if again.size:
                    F[row, again] = fits(up[row], q[again])
            queue = [int(i) for i in q[alive]]
        peak_queue = max(peak_queue, len(queue))

    def lose(i):
        """Task ``i`` failed for good: it and its unreleased descendants
        are unschedulable."""
        nonlocal unschedulable
        unschedulable += 1
        stack = list(children[i])
        while stack:
            c = stack.pop()
            if not dead[c]:
                dead[c] = True
                unschedulable += 1
                stack.extend(children[c])

    queue = [i for i in range(B) if pending[i] == 0]
    roots = set(queue)
    now = last_t = f(0)
    drain()
    while heap:
        now, _, kind, n, x, ep = heapq.heappop(heap)
        last_t = now
        if kind == "leave":
            up.remove(n)
            requeue = []
            for i in residents.pop(n):
                epoch[i] += 1
                evictions += 1
                e = min(int(np.floor((now - admit_t[i]) / dts[i] + 1e-9)),
                        mems[i].size)
                wasted += alloc_sum(i, e) * dts[i]
                attempts[i] += 1
                if attempts[i] >= max_attempts:
                    lose(i)
                else:
                    requeue.append(i)
            queue[0:0] = requeue
        elif kind == "join":
            up.append(n)
            cap_of[n] = x
            residents[n] = []
            for i in parked:
                starvation_s += float(now - park_t.pop(i))
            queue[0:0] = parked
            parked = []
        elif ep != epoch[x]:
            continue          # its task was evicted: no drain
        else:
            i = x
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
                attempts[i] += 1
                if attempts[i] < max_attempts and peak_demand[i] <= cap_max:
                    starts[i], peaks[i] = _ksplus_retry(
                        starts[i], peaks[i], f(v) * dts[i], f(bump))
                    need[i] = _alloc(starts[i:i + 1], peaks[i:i + 1],
                                     grid[i:i + 1])[0]
                    viol[i] = first_violation(i, False)
                    queue.append(i)
                else:
                    lose(i)
        drain()
    for i in parked:
        starvation_s += float(last_t - park_t.pop(i))
    first: Dict[int, float] = {}
    for t, _, j in placements:
        first.setdefault(j, t)
    waited = sum(1 for j, t in first.items() if t > 0 and index[j] in roots)
    return ChurnOutcome(placements, retries, unschedulable, finished,
                        float(makespan), float(wasted), peak_queue, waited,
                        evictions, B - finished - unschedulable,
                        starvation_s)
