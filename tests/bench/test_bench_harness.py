"""The benchmark harness on the CPU at tiny sizes: every cell runs through
the harness and comes out correct, the control and planted faults come
out not correct, and the command refuses to measure without a TPU."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import run

ROOT = run.ROOT
BENCH = run.load_json("BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]
KIND = {w: run.find_cell(BENCH, w)[3]["kind"] for w in CELLS}
COHORT = next(w for w in CELLS if KIND[w] == "cohort")


def _tiny(workload):
    """The cell at a size a CPU test holds: 4 samples a cohort, 8
    executions of history a family."""
    w, entry, cfg, traffic = run.find_cell(BENCH, workload)
    return w, entry, dict(cfg, history_per_family=8), dict(traffic, samples=4)


def _run(workload, seed=2**31 + 11, trace=False):
    lines = []
    res = run.run_cell(workload, seed, 0.0, trace, bench=BENCH,
                       require_tpu=False, cell=_tiny(workload),
                       setup_cache=False, out=lines.append)
    return res, lines


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_is_correct(workload):
    res, lines = _run(workload)
    assert json.loads(lines[-1]) == res
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    e2e, _ = run.metrics_of(BENCH, workload, [])
    assert set(res["metrics"]) == {m["name"] for m in e2e}
    assert list(res)[-1] == "checks"
    diag = json.loads(lines[0])["diagnostics"]
    assert diag["compiles_in_window"] == 0


# The CPU has no device plane: only the span readers find something.
SPAN_METRICS = {"cohort": {"loop_self_pct.replay", "drain_ms.replay"}}


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reports_span_metrics(workload):
    res, _ = _run(workload, trace=True)
    assert res["correct"] is True
    assert set(res["metrics"]) == SPAN_METRICS[KIND[workload]]
    assert all(0 < m["value"] for m in res["metrics"].values())
    assert res["device"]["window_s"] > 0


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_and_program_passes(workload):
    import importlib

    kind = importlib.import_module(f"bench.kinds.{KIND[workload]}")
    _, _, cfg, traffic = _tiny(workload)
    c = kind.build(dict(cfg, history_per_family=16),
                   dict(traffic, samples=8), 3)
    kind.warm(c, 0.5)
    kind.window(c, 0.5)
    outcomes = kind.program_outcomes(c)
    kind.release(c)
    ref = kind.reference(c)
    lower = kind.readings(c, outcomes, ref)
    upper = kind.control_readings(c, ref)
    lim = kind.LIMITS
    assert all(lower[k] <= lim[k] for k in lim)
    assert any(upper[k] > lim[k] for k in lim)


def _cohort_unchanged(orig):
    def fault(self, jobs, retry, *a, **kw):
        res = orig(self, jobs[:1], retry)
        res.placements, res.retries, res.finished = [], 0, 0
        res.total_wastage_gbs, res.makespan = 0.0, 0.0
        return res
    return fault


def _cohort_half(orig):
    def fault(self, jobs, retry, *a, **kw):
        return orig(self, jobs[:len(jobs) // 2], retry)
    return fault


def _cohort_altered(orig):
    def fault(self, jobs, retry, *a, **kw):
        res = orig(self, jobs, retry)
        t, nid, jid = res.placements[-1]
        res.placements[-1] = (t, nid ^ 1, jid)
        return res
    return fault


FAULTS = [
    ("cohort", "state_unchanged", _cohort_unchanged),
    ("cohort", "half_batch", _cohort_half),
    ("cohort", "answer_altered", _cohort_altered),
]


@pytest.mark.parametrize("kind,fault", [(k, f) for k, _, f in FAULTS],
                         ids=[f"{k}-{n}" for k, n, _ in FAULTS])
def test_planted_fault_is_not_correct(kind, fault, monkeypatch):
    from repro.sched import ClusterSim

    monkeypatch.setattr(ClusterSim, "run", fault(ClusterSim.run))
    res, _ = _run(COHORT, seed=5)
    assert res["correct"] is False


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_without_tpu():
    p = _command(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_command_refuses_in_bare_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for d in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, d), tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
