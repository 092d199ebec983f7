"""The plain reference of a replay under node churn
(``bench/reference/churn.py``), the churn kind's planted faults and the
reader of its span metric.

With no membership events the reference is the cohort reference exactly.
Each hand-made case, of two nodes and a few tasks, shows one rule of
eviction, re-admission, parking or rejoin, and the program's fused
replay makes the same decisions on it."""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from bench import run
from bench.reference import churn, replay

BUMP = 0.2


def _task(jid, peak, mem, length, parents=(), starts=(0.0,), peaks=None):
    """A task with a flat trace of ``mem`` GB (or the given trace) over
    ``length`` samples of 1 s, planned at ``peaks`` from ``starts``."""
    trace = (np.full(length, float(mem)) if np.ndim(mem) == 0
             else np.asarray(mem, np.float64))
    return churn.Task(jid, trace, 1.0, np.asarray(starts, np.float64),
                      np.asarray(peaks if peaks is not None else [peak],
                                 np.float64),
                      float(length), tuple(parents))


def _program(tasks, caps, events, max_attempts, trace=False):
    """The program's fused replay of the same tasks and events."""
    from repro.core import AllocationPlan, RetrySpec
    from repro.sched import ClusterSim, FaultEvent, Job, Node

    jobs = [Job(t.jid, "t", 1.0, t.mem, t.dt,
                AllocationPlan(t.starts, t.peaks), t.est_runtime,
                parents=t.parents) for t in tasks]
    faults = [FaultEvent(t, kind, nid, cap) for t, kind, nid, cap in events]
    sim = ClusterSim([Node(i, c) for i, c in enumerate(caps)],
                     max_attempts=max_attempts, engine="fused")
    return sim.run(jobs, RetrySpec("ksplus", bump=BUMP), faults=faults,
                   trace=trace)


def _both(tasks, caps, events, max_attempts=20):
    """The reference's outcome, checked against the program's."""
    got = churn.replay(tasks, caps, events, bump=BUMP,
                       max_attempts=max_attempts)
    res = _program(tasks, caps, events, max_attempts)
    assert got.placements == res.placements
    assert (got.retries, got.unschedulable, got.finished, got.makespan,
            got.evictions, got.starved, got.starvation_s) == (
        res.retries, res.unschedulable, res.finished, res.makespan,
        res.evictions, res.starved, res.starvation_s)
    assert got.wastage_gbs == pytest.approx(res.total_wastage_gbs,
                                            rel=1e-12)
    return got


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_no_events_is_the_cohort_reference(dtype):
    """On a small sarek cohort, an empty schedule gives exactly what
    ``replay.replay`` gives, in either precision: 24 samples on one
    node, so that roots wait in a standing queue and tasks retry."""
    kind = run.load_kind("cohort")
    bench = run.load_json("BENCHMARK.json")
    _, _, cfg, traffic = run.find_cell(bench, "sarek-cohort")
    c = kind.build(dict(cfg, history_per_family=8,
                        cluster=dict(cfg["cluster"], nodes=1)),
                   dict(traffic, samples=24), 5)
    want = kind._replay(c, c.plans, dtype)
    tasks = [churn.Task(jid, mem, 1.0, st, pk, est, par)
             for (jid, _, _, mem, par), (st, pk, est)
             in zip(c.rows, kind._submitted(c.rows, c.plans))]
    got = churn.replay(tasks, c.caps, (), bump=BUMP,
                       max_attempts=int(cfg["max_attempts"]), dtype=dtype)
    base = {f.name: getattr(got, f.name)
            for f in dataclasses.fields(replay.Outcome)}
    assert replay.Outcome(**base) == want
    assert want.retries > 0 and want.roots_waited > 0
    assert (got.evictions, got.starved, got.starvation_s) == (0, 0, 0.0)


def test_victims_go_in_admission_order_ahead_of_waiters():
    """Node 0 holds tasks 0 and 1 while task 2 waits; when it leaves,
    0 and 1 go back ahead of 2, in the order they were admitted."""
    tasks = [_task(0, 5.0, 4.0, 50), _task(1, 5.0, 4.0, 50),
             _task(2, 5.0, 4.0, 50), _task(3, 3.0, 2.5, 100)]
    events = [(10.0, "leave", 0, 0.0), (20.0, "join", 0, 10.0)]
    got = _both(tasks, [10.0, 4.0], events)
    assert got.placements == [(0.0, 0, 0), (0.0, 0, 1), (0.0, 1, 3),
                              (20.0, 0, 0), (20.0, 0, 1), (70.0, 0, 2)]
    assert got.evictions == 2 and got.finished == 4


@pytest.mark.parametrize("t_leave,wasted", [(7.5, 7 * 5.0 + 20 * 1.0),
                                            (20.0, 20 * 5.0 + 20 * 1.0)],
                         ids=["mid-sample", "at-its-end"])
def test_eviction_wastes_the_whole_samples_it_ran(t_leave, wasted):
    """An evicted task wastes its allocation over the whole samples since
    admission, no more than its length; a leave at the time the task
    would end comes first and kills it."""
    tasks = [_task(0, 5.0, 4.0, 20)]
    events = [(t_leave, "leave", 0, 0.0)]
    got = _both(tasks, [10.0, 10.0], events)
    assert got.placements == [(0.0, 0, 0), (t_leave, 1, 0)]
    assert got.evictions == 1 and got.finished == 1
    assert got.wastage_gbs == pytest.approx(wasted, rel=1e-12)


def test_out_of_attempts_dooms_the_child():
    """With two attempts, a task evicted twice is lost, and so is its
    child; the leave's span counts both as doomed."""
    tasks = [_task(0, 5.0, 4.0, 50), _task(1, 2.0, 1.0, 10, parents=(0,)),
             _task(2, 8.0, 7.0, 100)]
    events = [(5.0, "leave", 0, 0.0), (6.0, "join", 0, 10.0),
              (10.0, "leave", 0, 0.0), (11.0, "join", 0, 10.0)]
    got = _both(tasks, [10.0, 10.0], events, max_attempts=2)
    assert got.placements == [(0.0, 0, 0), (0.0, 1, 2), (6.0, 0, 0)]
    assert (got.evictions, got.unschedulable, got.finished) == (2, 2, 1)

    from repro.obs import trace as obs

    obs.clear()
    _program(tasks, [10.0, 10.0], events, 2, trace=True)
    leaves = [e["args"] for e in obs.events() if e["name"] == "cluster.leave"]
    obs.clear()
    assert leaves == [{"nid": 0, "evicted": 1, "doomed": 0},
                      {"nid": 0, "evicted": 1, "doomed": 2}]


@pytest.mark.parametrize("rejoin", [True, False], ids=["rejoins", "never"])
def test_task_no_node_can_hold_parks_until_a_join(rejoin):
    """Evicted from the only node large enough, a task parks; a join
    unparks it, and its wait counts into ``starvation_s``.  Never
    unparked, it is starved and charged up to the last event."""
    tasks = [_task(0, 8.0, 7.0, 50), _task(1, 3.0, 2.0, 100)]
    events = [(10.0, "leave", 0, 0.0)]
    if rejoin:
        events.append((25.0, "join", 0, 10.0))
    got = _both(tasks, [10.0, 4.0], events)
    if rejoin:
        assert got.placements[-1] == (25.0, 0, 0)
        assert (got.starved, got.starvation_s) == (0, 15.0)
    else:
        assert len(got.placements) == 2
        assert (got.starved, got.starvation_s) == (1, 90.0)


def test_joined_node_is_tried_last():
    """Task 0 is evicted while task 1 on node 1 leaves it no room; by the
    time node 0 is back, node 1 can hold it too, and first-fit takes node
    1, ahead of the node that joined."""
    tasks = [_task(0, None, [0.5] * 10 + [5.0] * 20, 30,
                   starts=(0.0, 10.0), peaks=(1.0, 6.0)),
             _task(1, 8.0, 7.5, 20)]
    events = [(5.0, "leave", 0, 0.0), (12.0, "join", 0, 10.0)]
    got = _both(tasks, [10.0, 10.0], events)
    assert got.placements == [(0.0, 0, 0), (0.0, 1, 1), (12.0, 1, 0)]


@pytest.mark.parametrize("platform,host_instant,exact", [
    ("tpu", True, True), ("tpu", False, False), ("cpu", False, True)])
def test_exact_on_device(platform, host_instant, exact):
    """A program without the host's admission instant cannot replay churn
    exactly on a TPU, and only there."""
    from repro.sched.admission import AdmissionState

    rows = AdmissionState if host_instant else type("Rows", (), {})
    kind = run.load_kind("churn")
    assert kind.exact_on_device(platform, rows) is exact


def test_program_without_host_instant_is_refused_on_a_tpu(monkeypatch):
    """The kind refuses such a program before any set-up, with a non-zero
    exit, so no result is printed."""
    import jax
    from repro.sched.admission import AdmissionState

    class Tpu:
        platform = "tpu"

    kind = run.load_kind("churn")
    monkeypatch.setattr(jax, "devices", lambda *a: [Tpu()])
    monkeypatch.delattr(AdmissionState, "_used_now")
    with pytest.raises(SystemExit, match="cannot run this cell"):
        kind.build(None, None, 0)


def test_churn_faults_extend_the_cohorts():
    cohort, kind = run.load_kind("cohort"), run.load_kind("churn")
    assert [n for n, _ in kind.FAULTS] == [n for n, _ in cohort.FAULTS] + [
        "faults_ignored", "evictions_miscounted"]


def _x(name, dur):
    return {"ph": "X", "name": name, "ts": 0.0, "dur": dur, "tid": 1}


FAULT_SPANS = [_x("cluster.run", 10_000.0), _x("admission.drain", 3_000.0),
               _x("cluster.leave", 300.0), _x("cluster.leave", 100.0),
               _x("cluster.join", 100.0)]


def test_fault_share_reads_the_spans():
    read = run.reader("loop_fault_pct.replay")
    assert read({"spans": FAULT_SPANS, "trace": None}) == pytest.approx(5.0)


@pytest.mark.parametrize("need", ["cluster.run", "cluster.leave",
                                  "cluster.join"])
def test_fault_share_absent_span_reads_nothing(need):
    read = run.reader("loop_fault_pct.replay")
    assert need in read.__globals__["SPANS"]
    spans = [e for e in FAULT_SPANS if e["name"] != need]
    assert read({"spans": spans, "trace": None}) is None
