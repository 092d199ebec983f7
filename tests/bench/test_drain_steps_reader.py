"""The reader of the events folded per admission drain, on hand-made
span lists: the mean of the ``steps`` arg, 1.0 for drains that carry
none (one per-event drain each), and nothing without a drain span."""

from __future__ import annotations

import pytest

from bench import run


def _drain(**args):
    ev = {"ph": "X", "name": "admission.drain", "ts": 0.0, "dur": 1.0,
          "tid": 1}
    if args:
        ev["args"] = args
    return ev


@pytest.mark.parametrize("spans,want", [
    ([_drain(q=3, steps=4, placed=2), _drain(q=1, steps=1),
      _drain(steps=1)], 2.0),
    ([_drain(q=3, placed=1), _drain()], 1.0),
    ([_drain(steps=5), {"ph": "X", "name": "cluster.run", "ts": 0.0,
                        "dur": 9.0, "tid": 1, "args": {"steps": 99}}], 5.0),
], ids=["folded", "per-event", "other-spans"])
def test_mean_steps_per_drain(spans, want):
    assert run.reader("drain_steps.replay")({"spans": spans}) == want


def test_no_drain_reads_nothing():
    read = run.reader("drain_steps.replay")
    assert read({"spans": []}) is None
    assert read({"spans": None}) is None
