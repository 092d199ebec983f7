"""What the benchmark's tests derive from ``BENCHMARK.json``."""

from __future__ import annotations

import pytest


@pytest.fixture
def span_metrics():
    """``span_metrics(run, bench, workload)``: the names of the cell's
    per-layer metrics whose ``source`` is ``program_span``, which a
    traced run reports on any backend.  ``run`` is the harness module of
    the checkout that holds the cell's files."""
    def derive(run, bench, workload):
        kind = run.load_kind(run.find_cell(bench, workload)[3]["kind"])
        _, layer = run.metrics_of(bench, workload, [kind.END_TO_END])
        return {m["name"] for m in layer if m["source"] == "program_span"}
    return derive
