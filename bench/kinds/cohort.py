"""Cohort replay: a samplesheet of ``samples`` pipeline runs released at t=0.

The cohort's executions, and the history KS+ is fitted on, are drawn
once from the mix's ``pool_seed``; ``--seed`` decides which sample takes
which execution of each family.  So every seed replays the same tasks
(the same placements and retries to make) released in another order.
Set-up fits KS+ per family on the history (the program's predictor),
plans every task of the cohort with it, and builds the per-sample DAG.
The window replays fresh copies of the same jobs back to back through
``ClusterSim.run``; every replay is compared with the plain reference
(``bench/reference``), which fits KS+ on the same history itself and
plans every task with its own fit: the program's plans against the
reference's, the total wastage against the reference's replay of its own
plans, and the whole schedule (placement log, retries, unschedulable
count, makespan) exactly against the reference's replay of the plans
the program submitted.  Plans that agree to the last few digits can tip
an admission tie and reorder a whole schedule, so the schedule is held
exact against the same plans (PERF.md gives the readings).
"""

from __future__ import annotations

import gc
import math
import multiprocessing as mp
import time
from concurrent.futures import ProcessPoolExecutor
from dataclasses import dataclass
from typing import List

import numpy as np

from bench import tracegen
from bench.reference import ksplus as ref_ksplus
from bench.reference import replay as ref_replay

END_TO_END = "placements_per_s"

# Each number compared and its limit (PERF.md gives the readings each
# limit was set from).  An exact comparison has the limit 0.
LIMITS = {
    "plan_gap": 1e-3,             # program plans vs the reference's own
    "schedule_mismatches": 0.0,   # vs the replay of the program's plans
    "wastage_gap": 1e-4,          # vs the replay of the reference's plans
}


@dataclass
class Cohort:
    cfg: dict
    traffic: dict
    rows: list            # (jid, family, input_gb, mem, parents)
    plans: dict           # family -> (starts, peaks, est) as submitted
    history: dict         # family -> training executions
    caps: np.ndarray
    retry: object         # the program's RetrySpec
    copies: list          # prebuilt job lists for the window
    results: list = None
    wall_s: float = 0.0


def _rows(cfg: dict, traffic: dict, seed: int):
    samples = int(traffic["samples"])
    execs = tracegen.executions(cfg, int(traffic["pool_seed"]), "cohort",
                                samples)
    fams = list(cfg["dag"])
    order = {fam: tracegen.order(seed, fam, samples) for fam in fams}
    rows = []
    for s in range(samples):
        for j, fam in enumerate(fams):
            e = execs[fam][order[fam][s]]
            parents = tuple(s * len(fams) + fams.index(p)
                            for p in cfg["dag"][fam])
            rows.append((s * len(fams) + j, fam, e.input_gb, e.mem, parents))
    return rows


def build(cfg: dict, traffic: dict, seed: int) -> Cohort:
    """Data from the pool and the seed, fits and plans by the program
    (set-up)."""
    from repro.core import KSPlus

    p = cfg["predictor"]
    hist = tracegen.history(cfg, int(traffic["pool_seed"]))
    rows = _rows(cfg, traffic, seed)
    plans, retry = {}, None
    for fam, ex in hist.items():
        m = KSPlus(k=int(p["k"]), peak_offset=p["peak_offset"],
                   start_offset=p["start_offset"],
                   last_peak_bump=p["last_peak_bump"])
        m.fit([e.mem for e in ex], [e.dt for e in ex],
              [e.input_gb for e in ex])
        xs = [r[2] for r in rows if r[1] == fam]
        st, pk = [], []
        for x in xs:
            plan = m.predict(x)
            st.append(plan.starts)
            pk.append(plan.peaks)
        est = [m.predict_runtime(x) for x in xs]
        plans[fam] = (np.asarray(st), np.asarray(pk), np.asarray(est))
        retry = m.retry_spec
    return Cohort(cfg, traffic, rows, plans, hist,
                  tracegen.node_capacities(cfg), retry, [])


def _submitted(rows: list, plans: dict):
    """Per job, in job order: (starts, peaks, est_runtime)."""
    seen = {f: 0 for f in plans}
    out = []
    for _, fam, _, _, _ in rows:
        st, pk, est = plans[fam]
        i = seen[fam]
        seen[fam] += 1
        out.append((st[i], pk[i], float(est[i])))
    return out


def make_jobs(c: Cohort) -> list:
    from repro.core import AllocationPlan
    from repro.sched import Job

    dt = float(c.cfg["dt_s"])
    return [Job(jid, fam, x, mem, dt, AllocationPlan(st, pk), est,
                parents=par)
            for (jid, fam, x, mem, par), (st, pk, est)
            in zip(c.rows, _submitted(c.rows, c.plans))]


def _sim(c: Cohort):
    from repro.sched import ClusterSim, Node

    cl = c.cfg["cluster"]
    return ClusterSim([Node(i, float(cap)) for i, cap in enumerate(c.caps)],
                      max_attempts=int(c.cfg["max_attempts"]),
                      engine=cl["engine"], drain=cl["drain"])


def warm(c: Cohort, seconds: float) -> dict:
    """One replay warms every shape the window uses (the same jobs give
    the same queue buckets), then enough job copies are built.  What
    set-up made is frozen out of the garbage collector, so the window's
    collections scan only what the replays allocate."""
    t0 = time.perf_counter()
    res = _sim(c).run(make_jobs(c), c.retry)
    took = time.perf_counter() - t0
    n = int(math.ceil(seconds / max(took, 1e-3))) + 2
    c.copies = [make_jobs(c) for _ in range(n)]
    gc.collect()
    gc.freeze()
    return {"warm_replay_s": took, "placements": len(res.placements)}


def window(c: Cohort, seconds: float, around=None) -> dict:
    """Whole replays back to back until ``seconds`` have passed; the one
    in flight at the deadline finishes inside the window."""
    sim = _sim(c)
    results = []
    t0 = time.perf_counter()
    while True:
        jobs = c.copies.pop() if c.copies else make_jobs(c)
        if around is None:
            results.append(sim.run(jobs, c.retry))
        else:
            with around("bench.replay"):
                results.append(sim.run(jobs, c.retry))
        if time.perf_counter() - t0 >= seconds:
            break
    c.wall_s = time.perf_counter() - t0
    c.results = results
    placed = sum(len(r.placements) for r in results)
    return {"metrics": {END_TO_END: placed / c.wall_s},
            "attempted": len(results) * len(c.rows),
            "failed": 0,
            "counts": {"replays": len(results), "placements": placed}}


def _plans(c: Cohort, dtype) -> dict:
    """The reference's KS+ fit of the history, with its arithmetic in
    ``dtype``, and its plans for every task of the cohort."""
    p = c.cfg["predictor"]
    plans = {}
    for fam, ex in c.history.items():
        model = ref_ksplus.fit([e.mem for e in ex], [e.dt for e in ex],
                               [e.input_gb for e in ex], p, dtype=dtype,
                               fit_dtype=np.float32)
        xs = [r[2] for r in c.rows if r[1] == fam]
        plans[fam] = tuple(np.asarray(a, np.float64)
                           for a in ref_ksplus.predict(model, xs))
    return plans


def _replay(c: Cohort, plans: dict, dtype) -> ref_replay.Outcome:
    """The reference's replay of ``plans``, computed in ``dtype``."""
    dt = float(c.cfg["dt_s"])
    tasks = [ref_replay.Task(jid, mem, dt, st, pk, est, par)
             for (jid, _, _, mem, par), (st, pk, est)
             in zip(c.rows, _submitted(c.rows, plans))]
    return ref_replay.replay(tasks, c.caps,
                             bump=float(c.cfg["predictor"]["last_peak_bump"]),
                             max_attempts=int(c.cfg["max_attempts"]),
                             dtype=dtype)


def _replays(c: Cohort, *jobs):
    """Reference replays ``(plans, dtype)``, each in a process of its own
    that imports numpy and the reference only."""
    ctx = mp.get_context("spawn")
    with ProcessPoolExecutor(len(jobs), mp_context=ctx) as ex:
        futs = [ex.submit(_replay, c, plans, dt) for plans, dt in jobs]
        return [f.result() for f in futs]


def reference(c: Cohort) -> dict:
    """The plain reference: its own fit and plans, its replay of its own
    plans (``outcome``) and, since plans that differ in the last digits
    can tip an admission tie, its replay of the plans the program
    submitted (``replayed``), which holds the schedule exact."""
    plans = _plans(c, np.float64)
    own, replayed = _replays(c, (plans, np.float64), (c.plans, np.float64))
    return {"plans": plans, "outcome": own, "replayed": replayed}


def _plan_gap(got: dict, want: dict) -> float:
    return max(ref_ksplus.plan_gap(got[fam], w) for fam, w in want.items())


def _outcome_of(res) -> ref_replay.Outcome:
    return ref_replay.Outcome(list(res.placements), res.retries,
                              res.unschedulable, res.finished,
                              float(res.makespan),
                              float(res.total_wastage_gbs), 0, 0)


def readings(c: Cohort, outcomes: List[ref_replay.Outcome],
             ref: dict) -> dict:
    """The numbers compared: the worst over every replay."""
    return _readings(c.plans, outcomes, ref)


def _readings(got_plans: dict, outcomes: List[ref_replay.Outcome],
              ref: dict) -> dict:
    own, same = ref["outcome"], ref["replayed"]
    mism = wgap = 0.0
    for o in outcomes:
        a, b = o.placements, same.placements
        n = sum(1 for x, y in zip(a, b) if x != y) + abs(len(a) - len(b))
        n += abs(o.retries - same.retries)
        n += abs(o.unschedulable - same.unschedulable)
        n += abs(o.finished - same.finished)
        n += o.makespan != same.makespan
        mism = max(mism, n)
        wgap = max(wgap, abs(o.wastage_gbs - own.wastage_gbs)
                   / max(abs(own.wastage_gbs), 1e-30))
    return {"plan_gap": _plan_gap(got_plans, ref["plans"]),
            "schedule_mismatches": float(mism), "wastage_gap": wgap}


def program_outcomes(c: Cohort) -> List[ref_replay.Outcome]:
    """What the window's replays produced, as plain data."""
    return [_outcome_of(r) for r in c.results]


def release(c: Cohort) -> None:
    """Drop the program's objects; what stays is plain data."""
    c.results, c.copies, c.retry = None, [], None
    gc.unfreeze()


def control_readings(c: Cohort, ref: dict) -> dict:
    """The control in the program's place: the reference one precision
    below the configuration, its fit in bfloat16 and its replay in
    float32; the schedule is held against the float64 replay of its own
    plans."""
    import ml_dtypes

    plans = _plans(c, ml_dtypes.bfloat16)
    out, replayed = _replays(c, (plans, np.float32), (plans, np.float64))
    return _readings(plans, [out], dict(ref, replayed=replayed))


def diagnostics(c: Cohort, ref: dict) -> dict:
    o = ref["outcome"]
    return {"tasks": len(c.rows), "peak_queue": o.peak_queue,
            "roots_waited": o.roots_waited, "retries": o.retries,
            "unschedulable": o.unschedulable}


def _unchanged(run):
    def fault(self, jobs, retry, *a, **kw):
        res = run(self, jobs[:1], retry)
        res.placements, res.retries, res.finished = [], 0, 0
        res.total_wastage_gbs, res.makespan = 0.0, 0.0
        return res
    return fault


def _half(run):
    def fault(self, jobs, retry, *a, **kw):
        return run(self, jobs[:len(jobs) // 2], retry)
    return fault


def _altered(run):
    def fault(self, jobs, retry, *a, **kw):
        res = run(self, jobs, retry)
        t, nid, jid = res.placements[-1]
        res.placements[-1] = (t, nid ^ 1, jid)
        return res
    return fault


def _planted(wrap):
    def plant(monkeypatch):
        from repro.sched import ClusterSim
        monkeypatch.setattr(ClusterSim, "run", wrap(ClusterSim.run))
    return plant


# Faults planted in the timed path, each of which a run has to find not
# correct (tests/bench): a name and a factory that plants the fault
# through pytest's ``monkeypatch`` for the rest of the test.  A replay
# that returns its state unchanged, one that leaves out half of its
# jobs, and one whose last placement names another node.
FAULTS = [
    ("state_unchanged", _planted(_unchanged)),
    ("half_batch", _planted(_half)),
    ("answer_altered", _planted(_altered)),
]
