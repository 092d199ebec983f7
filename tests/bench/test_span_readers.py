"""The readers of the drain's split and the loop's retry share, on
hand-made span lists: their values, and nothing where a span is absent
or the trace has no device plane."""

from __future__ import annotations

import pytest

from bench import profile, run

CHIP = profile.Trace({"/device:TPU:0": {"ops": [("op", 0.0, 1.0)],
                                        "modules": []}})


def _x(name, dur):
    return {"ph": "X", "name": name, "ts": 0.0, "dur": dur, "tid": 1}


# Two drains (durations in us), one of which went twice through the
# program (a backlog wider than the drain's cap), and one re-plan.
SPANS = [_x("cluster.run", 20_000.0),
         _x("admission.drain", 4_000.0), _x("admission.drain", 5_000.0),
         _x("admission.drain.operands", 1_000.0),
         _x("admission.drain.operands", 1_500.0),
         _x("admission.drain.operands", 500.0),
         _x("admission.drain.launch", 200.0),
         _x("admission.drain.launch", 300.0),
         _x("admission.drain.launch", 100.0),
         _x("admission.drain.readback", 600.0),
         _x("admission.drain.readback", 900.0),
         _x("admission.drain.readback", 300.0),
         _x("cluster.retry", 500.0)]

EXPECTED = {"drain_operands_ms.replay": 1.5,
            "drain_launch_ms.replay": 0.3,
            "drain_readback_ms.replay": 0.9,
            "loop_retry_pct.replay": 2.5}
NEEDS = {"drain_operands_ms.replay": "admission.drain.operands",
         "drain_launch_ms.replay": "admission.drain.launch",
         "drain_readback_ms.replay": "admission.drain.readback",
         "loop_retry_pct.replay": "cluster.retry"}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reads_the_spans(metric):
    got = run.reader(metric)({"spans": SPANS, "trace": CHIP})
    assert got == pytest.approx(EXPECTED[metric])


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_absent_span_reads_nothing(metric):
    spans = [e for e in SPANS if e["name"] != NEEDS[metric]]
    assert run.reader(metric)({"spans": spans, "trace": CHIP}) is None
    assert run.reader(metric)({"spans": [], "trace": CHIP}) is None


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_trace_without_device_reads_nothing(metric):
    read = run.reader(metric)
    assert read({"spans": SPANS, "trace": None}) is None
    assert read({"spans": SPANS, "trace": profile.Trace({})}) is None


def test_drain_split_is_within_the_drain():
    ctx = {"spans": SPANS, "trace": CHIP}
    parts = sum(run.reader(m)(ctx) for m in EXPECTED if "drain" in m)
    assert parts <= run.reader("drain_ms.replay")(ctx)
