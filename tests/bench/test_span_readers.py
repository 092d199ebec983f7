"""The readers of the drain's split and the loop's retry share, on
hand-made span lists: their values, the same with or without a device
plane in the trace, and nothing where a span they need is absent."""

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
TRACES = {"chip": CHIP, "no-trace": None, "no-device": profile.Trace({})}


@pytest.mark.parametrize("trace", TRACES.values(), ids=list(TRACES))
@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reads_the_spans(metric, trace):
    got = run.reader(metric)({"spans": SPANS, "trace": trace})
    assert got == pytest.approx(EXPECTED[metric])


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_absent_span_reads_nothing(metric):
    read = run.reader(metric)
    for need in read.__globals__["SPANS"]:
        spans = [e for e in SPANS if e["name"] != need]
        assert read({"spans": spans, "trace": CHIP}) is None, need
    assert read({"spans": [], "trace": CHIP}) is None


def test_drain_split_is_within_the_drain():
    ctx = {"spans": SPANS, "trace": CHIP}
    parts = sum(run.reader(m)(ctx) for m in EXPECTED if "drain" in m)
    assert parts <= run.reader("drain_ms.replay")(ctx)
