"""The benchmark harness on the CPU at tiny sizes: every cell runs through
the harness and comes out correct, the control and planted faults come
out not correct, and the command refuses to measure without a TPU."""

from __future__ import annotations

import json
import math
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


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reports_span_metrics(workload, span_metrics,
                                         monkeypatch):
    """Every one of the cell's ``program_span`` metrics is due (the run
    recorded each span in its reader's ``SPANS``) and reads a finite,
    positive value, and no other metric reads anything: the CPU's trace
    has no device plane, so ``device_trace`` metrics stay silent."""
    from repro.obs import trace as obs

    spans = []
    events = obs.events

    def kept():
        got = events()
        spans.extend(got)
        return got
    monkeypatch.setattr(obs, "events", kept)
    res, _ = _run(workload, trace=True)
    assert res["correct"] is True
    assert res["device"]["window_s"] > 0
    recorded = {e["name"] for e in spans}
    expected = span_metrics(run, BENCH, workload)
    missing = {m: sorted(set(run.reader(m).__globals__["SPANS"]) - recorded)
               for m in expected}
    assert not any(missing.values()), missing
    got = res["metrics"]
    assert set(got) == expected
    assert all(math.isfinite(m["value"]) and m["value"] > 0
               for m in got.values()), got


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_and_program_passes(workload):
    kind = run.load_kind(KIND[workload])
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


# Each kind a cell uses, with the first cell of that kind.
KINDS = {KIND[w]: w for w in reversed(CELLS)}
FAULTS = [(k, n, plant) for k in sorted(KINDS)
          for n, plant in run.load_kind(k).FAULTS]


@pytest.mark.parametrize("kind,plant", [(k, p) for k, _, p in FAULTS],
                         ids=[f"{k}-{n}" for k, n, _ in FAULTS])
def test_planted_fault_is_not_correct(kind, plant, monkeypatch):
    plant(monkeypatch)
    res, _ = _run(KINDS[kind], seed=5)
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
