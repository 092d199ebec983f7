"""The benchmark's data layout: ``BENCHMARK.json`` against its contract,
discovery of configurations, mixes and metrics by name, the reduction of
a recorded device trace, and the table of peaks."""

from __future__ import annotations

import importlib.util
import json
import os
import re
import shutil
import sys

import pytest

from bench import peaks, profile, run

ROOT = run.ROOT
BENCH = run.load_json("BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
FIXTURE = os.path.join(ROOT, "bench", "fixtures", "eager-tiny.xplane.pb.gz")


def test_contract_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for d in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, d))
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
             + [w["traffic"] for w in BENCH["workloads"]])
    assert all(NAME.match(n) for n in names)
    for group in ("configs", "workloads"):
        got = [x["name"] for x in BENCH[group]]
        assert len(got) == len(set(got))
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())


def _chips_admitted(bench):
    """Each cell takes 1 chip or 4; at most half of the cells, rounded
    down, take 4, though one always may."""
    chips = [w["chips"] for w in bench["workloads"]]
    return (set(chips) <= {1, 4}
            and chips.count(4) <= max(1, len(chips) // 2))


def test_every_cell_reports_what_the_contract_asks():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert _chips_admitted(BENCH)
    for w in BENCH["workloads"]:
        got_e2e, layer = run.metrics_of(BENCH, w["name"], [])
        assert "setup_s" in {m["name"] for m in got_e2e}
        assert len(got_e2e) >= 2 and layer
        for m in layer:
            assert m["moves"] in e2e
            assert m["moves"] in {x["name"] for x in got_e2e}


@pytest.mark.parametrize("chips,admitted", [
    ([4], True), ([1, 4], True), ([1, 1, 4, 4], True),
    ([1, 4, 4], False), ([2], False)],
    ids=["single-four", "one-of-two", "half", "over-half", "two-chips"])
def test_chip_counts(chips, admitted):
    cell = dict(BENCH["workloads"][0])
    bench = dict(BENCH, workloads=[dict(cell, name=f"c{i}", chips=n)
                                   for i, n in enumerate(chips)])
    assert _chips_admitted(bench) is admitted


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_files_are_found_by_name(workload):
    w, entry, cfg, traffic = run.find_cell(BENCH, workload)
    assert cfg["name"] == w["config"]
    assert set(entry["reduced"]) <= set(cfg)
    assert os.path.isfile(os.path.join(ROOT, "bench", "kinds",
                                       traffic["kind"] + ".py"))
    _, layer = run.metrics_of(BENCH, workload, [])
    for m in layer:
        assert callable(run.reader(m["name"]))


def _copy_bench(tmp_path):
    """``bench/`` copied under ``tmp_path``, its harness loaded from the
    copy, and the bytes of every file in it."""
    copy = tmp_path / "bench"
    shutil.copytree(os.path.join(ROOT, "bench"), copy,
                    ignore=shutil.ignore_patterns("__pycache__"))
    saved = list(sys.path)
    try:
        spec = importlib.util.spec_from_file_location("bench_copy_run",
                                                      copy / "run.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path[:] = saved
    assert mod.ROOT == str(tmp_path)
    return copy, mod, _contents(copy)


def _contents(copy):
    return {p: p.read_bytes() for p in copy.rglob("*") if p.is_file()}


def _with_cell(bench, config, cell, traffic):
    """``bench`` with one more configuration and cell, the cell added to
    ``placements_per_s``'s list as a later change would add it."""
    bench = json.loads(json.dumps(bench))
    bench["configs"].append(dict(bench["configs"][0], name=config,
                                 file=f"bench/configs/{config}.json"))
    bench["workloads"].append({"name": cell, "config": config,
                               "traffic": traffic, "chips": 1, "why": "x"})
    for m in bench["end_to_end"]:
        if m["name"] == "placements_per_s":
            m["workloads"].append(cell)
    return bench


def test_new_config_mix_and_metric_are_found_without_edits(tmp_path):
    """A later change adds files and entries; no existing file changes."""
    copy, mod, before = _copy_bench(tmp_path)
    shutil.copy(copy / "configs" / "sarek.json", copy / "configs" / "new.json")
    (copy / "traffic" / "cohort-64.json").write_text(
        json.dumps({"kind": "cohort", "samples": 64}))
    (copy / "metrics" / "replays.replay.py").write_text(
        "def read(ctx):\n    return float(ctx['counts']['replays'])\n")
    bench = _with_cell(BENCH, "new", "new-cohort", "cohort-64")
    bench["per_layer"].append({"name": "replays.replay", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "event loop",
                               "moves": "placements_per_s",
                               "workloads": ["new-cohort"]})
    w, _, cfg, traffic = mod.find_cell(bench, "new-cohort")
    assert cfg["name"] == "sarek" and traffic["samples"] == 64
    _, layer = mod.metrics_of(bench, "new-cohort", [])
    assert "replays.replay" in [m["name"] for m in layer]
    assert mod.reader("replays.replay")({"counts": {"replays": 3}}) == 3.0
    assert before == {p: v for p, v in _contents(copy).items()
                      if p in before}


def test_new_kind_is_found_without_edits(tmp_path, span_metrics):
    """A cell of a new traffic kind, with a span metric of its own, comes
    in as new files and entries: the kind is loaded from its file, the
    metric joins the cell's traced-run expectation, and no existing file
    changes."""
    copy, mod, before = _copy_bench(tmp_path)
    shutil.copy(copy / "kinds" / "cohort.py", copy / "kinds" / "fresh.py")
    (copy / "traffic" / "fresh-8.json").write_text(
        json.dumps({"kind": "fresh", "samples": 8, "pool_seed": 0}))
    shutil.copy(copy / "configs" / "eager.json", copy / "configs" / "new.json")
    (copy / "metrics" / "retry_count.fresh.py").write_text(
        'SPANS = ("cluster.retry",)\n\n\ndef read(ctx):\n'
        '    return float(sum(e["name"] in SPANS\n'
        '                     for e in ctx["spans"])) or None\n')
    bench = _with_cell(BENCH, "new", "new-fresh", "fresh-8")
    bench["per_layer"].append({"name": "retry_count.fresh", "unit": "1",
                               "better": "lower", "source": "program_span",
                               "layer": "event loop",
                               "moves": "placements_per_s",
                               "workloads": ["new-fresh"]})
    try:
        w, _, cfg, traffic = mod.find_cell(bench, "new-fresh")
        assert cfg["name"] == "eager" and traffic["kind"] == "fresh"
        kind = mod.load_kind("fresh")
        assert kind.__name__ == "bench.kinds.fresh"
        assert kind.__file__ == str(copy / "kinds" / "fresh.py")
        assert mod.load_kind("fresh") is kind
        assert kind.LIMITS and [n for n, _ in kind.FAULTS]
        e2e, layer = mod.metrics_of(bench, "new-fresh", [kind.END_TO_END])
        assert "placements_per_s" in [m["name"] for m in e2e]
        assert "retry_count.fresh" in [m["name"] for m in layer]
        assert "retry_count.fresh" in span_metrics(mod, bench, "new-fresh")
        run.load_kind("cohort")
        with pytest.raises(RuntimeError, match="already loaded"):
            mod.load_kind("cohort")
    finally:
        sys.modules.pop("bench.kinds.fresh", None)
    spans = [{"name": "cluster.retry", "dur": 1.0}] * 2
    assert mod.reader("retry_count.fresh")({"spans": spans}) == 2.0
    assert before == {p: v for p, v in _contents(copy).items()
                      if p in before}


def test_recorded_trace_reduces():
    tr = profile.load(FIXTURE)
    assert list(tr.devices) == ["/device:TPU:0"]
    busy = tr.busy_s()
    assert busy is not None and busy > 0
    ops = tr.op_totals(10)
    assert ops and all(v > 0 for _, v in ops)
    assert sum(v for _, v in ops) <= busy * 1.000001 or len(ops) == 10
    s, runs = tr.module_s(lambda n: n.startswith("jit_kernel"))
    assert runs > 0 and 0 < s <= busy * 1.000001
    gaps = tr.idle_gaps(3)
    assert gaps and all(g > 0 for _, g in gaps)
    assert [g for _, g in gaps] == sorted((g for _, g in gaps), reverse=True)


def test_reduction_arithmetic():
    tr = profile.Trace({"/device:TPU:0": {
        "ops": [("a", 0.0, 10.0), ("b", 5.0, 10.0), ("a", 30.0, 5.0)],
        "modules": [("jit_x", 0.0, 15.0), ("jit_y", 30.0, 5.0)]}})
    assert tr.busy_s() == pytest.approx(20e-9)
    assert [n for n, _ in tr.op_totals()] == ["a", "b"]
    assert [v for _, v in tr.op_totals()] == pytest.approx([15e-9, 10e-9])
    assert tr.module_s(lambda n: n == "jit_y") == (pytest.approx(5e-9), 1)
    (name, gap), = tr.idle_gaps()
    assert name == "host between jit_x and jit_y"
    assert gap == pytest.approx(15e-9)
    assert profile.Trace({}).busy_s() is None


def test_peaks_table():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
