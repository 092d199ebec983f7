"""Cohort replay under node churn: a samplesheet on a preemptible cluster.

The cohort kind (``bench/kinds/cohort.py``) on a cluster whose nodes are
reclaimed and come back while the cohort runs.  Its pool, its ``--seed``
shuffle, the program's fits and plans, the reference's own fits and plans
and ``LIMITS`` are the cohort's.  Set-up also draws the configuration's
churn process (``cluster.churn``: ``FaultSchedule.node_churn`` at
``leaves_per_node_hour`` per node over ``horizon_s``, each node down for
Exp(``mean_down_s``), all on the traces' clock) once from the mix's
``fault_seed``, so the fault events are the same on every ``--seed``.
Every replay, warm and timed, runs ``ClusterSim.run`` under that schedule.

The plain reference (``bench/reference/churn.py``) replays the same
events.  The schedule comparison of the cohort kind gains the evictions,
the starved tasks and the time spent parked, all exact: a reclaimed node
kills its residents, spends an attempt of each, charges the wasted
allocation and requeues them ahead of the waiters, and one eviction out
of place reorders the rest of the schedule.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import multiprocessing as mp
import time
from concurrent.futures import ProcessPoolExecutor
from typing import List

import numpy as np

from bench.kinds import cohort
from bench.kinds.cohort import END_TO_END, LIMITS, make_jobs  # noqa: F401
from bench.reference import churn as ref_churn


@dataclasses.dataclass
class Spot(cohort.Cohort):
    faults: object = None    # the program's FaultSchedule
    events: tuple = ()       # the same events as plain (t, kind, nid, cap)


def _schedule(cfg: dict, traffic: dict, caps):
    """The configuration's churn process over nodes of ``caps``, drawn
    from the mix's ``fault_seed``: a ``FaultSchedule``."""
    from repro.sched import FaultSchedule, Node

    churn = cfg["cluster"]["churn"]
    if churn["process"] != "node_churn":
        raise ValueError(f"unknown churn process {churn['process']!r}")
    nodes = [Node(i, float(cap)) for i, cap in enumerate(caps)]
    return FaultSchedule.node_churn(
        nodes, rate=float(churn["leaves_per_node_hour"]) * len(nodes) / 3600,
        horizon=float(churn["horizon_s"]), seed=int(traffic["fault_seed"]),
        mean_down=float(churn["mean_down_s"]))


def exact_on_device(platform: str, admission_state) -> bool:
    """Whether a program whose admission rows are ``admission_state`` can
    replay churn exactly on a ``platform`` device.

    Churn puts event times at fractions of a second, and a re-admission
    at such a time makes a resident's ``now - t0`` fall exactly on one of
    its plan's segment starts.  A TPU's float64 is emulated in about 49
    bits and can round that tie to the other segment than IEEE float64
    does, so a program that evaluates the admission instant on the device
    places differently from the reference on some seeds.  A program that
    takes that instant from the host (``AdmissionState._used_now``) is
    exact; so is any program on a device with IEEE float64."""
    return platform != "tpu" or hasattr(admission_state, "_used_now")


def build(cfg: dict, traffic: dict, seed: int) -> Spot:
    """The cohort's set-up, and the fault schedule (set-up).  A program
    that cannot replay churn exactly on this device (see
    :func:`exact_on_device`) is refused first, before any set-up, and the
    run exits non-zero: it would only print a result that is not
    correct."""
    import jax
    from repro.sched.admission import AdmissionState

    platform = jax.devices()[0].platform
    if not exact_on_device(platform, AdmissionState):
        raise SystemExit(
            f"bench: this program evaluates the admission instant in the "
            f"{platform}'s emulated float64, which is not exact at the "
            f"fractional event times of a churned replay; it cannot run "
            f"this cell; nothing was measured")
    c = cohort.build(cfg, traffic, seed)
    faults = _schedule(cfg, traffic, c.caps)
    return Spot(**{f.name: getattr(c, f.name)
                   for f in dataclasses.fields(c)},
                faults=faults,
                events=tuple((e.t, e.kind, e.nid, e.capacity_gb)
                             for e in faults))


def warm(c: Spot, seconds: float) -> dict:
    """As the cohort's: one churned replay warms every shape the window
    uses, then enough job copies are built and set-up is frozen out of
    the garbage collector."""
    t0 = time.perf_counter()
    res = cohort._sim(c).run(make_jobs(c), c.retry, faults=c.faults)
    took = time.perf_counter() - t0
    n = int(math.ceil(seconds / max(took, 1e-3))) + 2
    c.copies = [make_jobs(c) for _ in range(n)]
    gc.collect()
    gc.freeze()
    return {"warm_replay_s": took, "placements": len(res.placements)}


def window(c: Spot, seconds: float, around=None) -> dict:
    """Whole churned replays back to back until ``seconds`` have passed;
    the one in flight at the deadline finishes inside the window."""
    sim = cohort._sim(c)
    results = []
    t0 = time.perf_counter()
    while True:
        jobs = c.copies.pop() if c.copies else make_jobs(c)
        if around is None:
            results.append(sim.run(jobs, c.retry, faults=c.faults))
        else:
            with around("bench.replay"):
                results.append(sim.run(jobs, c.retry, faults=c.faults))
        if time.perf_counter() - t0 >= seconds:
            break
    c.wall_s = time.perf_counter() - t0
    c.results = results
    placed = sum(len(r.placements) for r in results)
    return {"metrics": {END_TO_END: placed / c.wall_s},
            "attempted": len(results) * len(c.rows),
            "failed": 0,
            "counts": {"replays": len(results), "placements": placed}}


def _replay(c: Spot, plans: dict, dtype) -> ref_churn.ChurnOutcome:
    """The reference's churned replay of ``plans``, computed in
    ``dtype``."""
    dt = float(c.cfg["dt_s"])
    tasks = [ref_churn.Task(jid, mem, dt, st, pk, est, par)
             for (jid, _, _, mem, par), (st, pk, est)
             in zip(c.rows, cohort._submitted(c.rows, plans))]
    return ref_churn.replay(tasks, c.caps, c.events,
                            bump=float(c.cfg["predictor"]["last_peak_bump"]),
                            max_attempts=int(c.cfg["max_attempts"]),
                            dtype=dtype)


def _replays(c: Spot, *jobs):
    """Reference replays ``(plans, dtype)``, each in a process of its own
    that imports numpy and the reference only."""
    ctx = mp.get_context("spawn")
    with ProcessPoolExecutor(len(jobs), mp_context=ctx) as ex:
        futs = [ex.submit(_replay, c, plans, dt) for plans, dt in jobs]
        return [f.result() for f in futs]


def reference(c: Spot) -> dict:
    """As the cohort's: the reference's own fit and plans, its churned
    replay of them (``outcome``), and its churned replay of the plans the
    program submitted (``replayed``), which holds the schedule exact."""
    plans = cohort._plans(c, np.float64)
    own, replayed = _replays(c, (plans, np.float64), (c.plans, np.float64))
    return {"plans": plans, "outcome": own, "replayed": replayed}


def _readings(got_plans: dict, outcomes: List[ref_churn.ChurnOutcome],
              ref: dict) -> dict:
    """The cohort's numbers, worst over the replays, with each replay's
    evictions, starved tasks and starvation time counted into
    ``schedule_mismatches``."""
    same = ref["replayed"]
    worst: dict = {}
    for o in outcomes:
        got = cohort._readings(got_plans, [o], ref)
        got["schedule_mismatches"] += float(
            abs(o.evictions - same.evictions) + abs(o.starved - same.starved)
            + (o.starvation_s != same.starvation_s))
        worst = {k: max(v, worst.get(k, v)) for k, v in got.items()}
    return worst


def readings(c: Spot, outcomes: List[ref_churn.ChurnOutcome],
             ref: dict) -> dict:
    """The numbers compared: the worst over every replay."""
    return _readings(c.plans, outcomes, ref)


def _outcome_of(res) -> ref_churn.ChurnOutcome:
    return ref_churn.ChurnOutcome(**vars(cohort._outcome_of(res)),
                                  evictions=res.evictions,
                                  starved=res.starved,
                                  starvation_s=float(res.starvation_s))


def program_outcomes(c: Spot) -> List[ref_churn.ChurnOutcome]:
    """What the window's replays produced, as plain data."""
    return [_outcome_of(r) for r in c.results]


def release(c: Spot) -> None:
    """Drop the program's objects, the fault schedule among them; what
    stays is plain data."""
    cohort.release(c)
    c.faults = None


def control_readings(c: Spot, ref: dict) -> dict:
    """As the cohort's: the reference one precision below the
    configuration in the program's place, its fit in bfloat16 and its
    churned replay in float32, the schedule held against the float64
    churned replay of its own plans."""
    import ml_dtypes

    plans = cohort._plans(c, ml_dtypes.bfloat16)
    out, replayed = _replays(c, (plans, np.float32), (plans, np.float64))
    return _readings(plans, [out], dict(ref, replayed=replayed))


def diagnostics(c: Spot, ref: dict) -> dict:
    o = ref["outcome"]
    return {**cohort.diagnostics(c, ref),
            "leaves": sum(e[1] == "leave" for e in c.events),
            "joins": sum(e[1] == "join" for e in c.events),
            "evictions": o.evictions, "starved": o.starved,
            "starvation_s": o.starvation_s}


def _ignored(run):
    def fault(self, jobs, retry, *a, faults=None, **kw):
        return run(self, jobs, retry, *a, **kw)
    return fault


def _miscounted(run):
    def fault(self, jobs, retry, *a, **kw):
        res = run(self, jobs, retry, *a, **kw)
        res.evictions -= 1
        return res
    return fault


# The cohort's planted faults, and two of the churn: a replay that drops
# its fault schedule, and one that counts an eviction fewer.
FAULTS = cohort.FAULTS + [
    ("faults_ignored", cohort._planted(_ignored)),
    ("evictions_miscounted", cohort._planted(_miscounted)),
]
