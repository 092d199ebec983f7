"""repro.obs: tracer, metrics registry, exporters, and the
zero-perturbation contract.

The tracing contract under test: with observability off, instrumented
hot paths record *nothing* (one module-attribute check); with it on,
spans/instants/dispatch tags land in the bounded ring and metrics in the
global registry — and a traced replay stays **bitwise identical** to an
untraced one on every decision log (placements, retries, evictions) and
on served plans.  Exporters must round-trip: Chrome-trace JSON and JSONL
both reload through ``read_events``, ``summarize`` reports every span
name, and the Prometheus text form is well-shaped.
"""

import json
import os
import threading

import numpy as np
import pytest

from repro import obs
from repro.analysis import contracts
from repro.core import AllocationPlan, RetrySpec
from repro.obs.__main__ import main as obs_cli
from repro.sched import ClusterSim, FaultSchedule, Job, Node


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends disabled with an empty default-size
    ring and an empty registry (``enable(ring=N)`` resizes the module
    ring, so tests that shrink it must not leak that into the next)."""

    def reset():
        obs.disable()
        if obs.trace._ring.maxlen != obs.trace.DEFAULT_RING:
            obs.trace._ring = type(obs.trace._ring)(
                maxlen=obs.trace.DEFAULT_RING)
        obs.clear()
        obs.REGISTRY.clear()

    reset()
    yield
    reset()


# -------------------------------------------------------------------- tracer
class TestTracer:
    def test_disabled_records_nothing(self):
        with obs.span("a", x=1) as sp:
            sp.add(y=2)
        obs.instant("b")
        contracts.record_dispatch("some.tag")
        assert obs.events() == []

    def test_span_event_shape(self):
        with obs.tracing():
            with obs.span("admission.drain", q=3) as sp:
                sp.add(placed=2)
        (ev,) = obs.events()
        assert ev["ph"] == "X" and ev["name"] == "admission.drain"
        assert ev["dur"] >= 0.0 and ev["ts"] >= 0.0
        assert ev["args"] == {"q": 3, "placed": 2}
        assert ev["tid"] == threading.get_ident()

    def test_nesting_orders_inner_first(self):
        with obs.tracing():
            with obs.span("outer"):
                with obs.span("inner"):
                    pass
        names = [e["name"] for e in obs.events()]
        assert names == ["inner", "outer"]

    def test_thread_local_stacks(self):
        """Concurrent spans on two threads never cross-attribute."""
        with obs.tracing():
            barrier = threading.Barrier(2)

            def worker(name):
                with obs.span(name):
                    barrier.wait(timeout=5)
                    contracts.record_dispatch(f"tag.{name}")
                    barrier.wait(timeout=5)

            t = threading.Thread(target=worker, args=("t1",))
            t.start()
            worker("t0")
            t.join()
        by_name = {e["name"]: e for e in obs.events()}
        assert by_name["t0"]["dispatches"] == {"tag.t0": 1}
        assert by_name["t1"]["dispatches"] == {"tag.t1": 1}
        assert by_name["t0"]["tid"] != by_name["t1"]["tid"]

    def test_ring_is_bounded(self):
        with obs.tracing(ring=16):
            for i in range(100):
                obs.instant("e", i=i)
        evs = obs.events()
        assert len(evs) == 16
        assert evs[-1]["args"] == {"i": 99}  # newest survive

    def test_tracing_restores_prior_state(self):
        with obs.tracing():
            with obs.tracing():
                assert obs.trace.enabled
            assert obs.trace.enabled  # inner exit keeps outer's on
        assert not obs.trace.enabled

    def test_dispatch_attributed_to_open_span(self):
        with obs.tracing():
            with obs.span("work"):
                contracts.record_dispatch("fused.drain", 2)
                contracts.record_dispatch("fused.drain")
        (ev,) = obs.events()
        assert ev["dispatches"] == {"fused.drain": 3}

    def test_dispatch_without_span_is_loose_instant(self):
        with obs.tracing():
            contracts.record_dispatch("fused.drain")
        (ev,) = obs.events()
        assert ev["ph"] == "i" and ev["name"] == "dispatch:fused.drain"

    def test_disable_removes_dispatch_hook(self):
        with obs.tracing():
            assert contracts._obs_dispatch_hook is not None
        assert contracts._obs_dispatch_hook is None
        contracts.record_dispatch("late.tag")
        assert obs.events() == []


# ------------------------------------------------------------------- metrics
class TestMetrics:
    def test_counter_labels(self):
        c = obs.counter("serve.requests")
        c.inc(kind="predict")
        c.inc(2, kind="predict")
        c.inc(kind="evaluate")
        assert c.value(kind="predict") == 3
        assert c.value(kind="evaluate") == 1
        assert c.value(kind="absent") == 0

    def test_gauge_last_write_wins(self):
        g = obs.gauge("serve.queue_depth")
        g.set(5)
        g.set(2)
        assert g.value() == 2.0

    def test_histogram_buckets_cumulative(self):
        h = obs.hist("lat", buckets=(1.0, 10.0, 100.0))
        for v in (0.5, 0.7, 5.0, 1000.0):
            h.observe(v)
        assert h.count() == 4
        (row,) = h.snapshot()["values"]
        assert row["cumulative"] == [2, 3, 3, 4]  # last == count
        assert row["sum"] == pytest.approx(1006.2)

    def test_histogram_rejects_infinite_buckets(self):
        with pytest.raises(ValueError):
            obs.REGISTRY.hist("bad", buckets=(1.0, float("inf")))

    def test_series_bounded_sim_time(self):
        s = obs.REGISTRY.series("curve", maxlen=4)
        for t in range(10):
            s.append(float(t), t * 2.0)
        assert s.points() == [(6.0, 12.0), (7.0, 14.0),
                              (8.0, 16.0), (9.0, 18.0)]

    def test_registry_kind_conflict_is_loud(self):
        obs.counter("x")
        with pytest.raises(TypeError, match="already registered"):
            obs.gauge("x")

    def test_get_or_create_returns_same_object(self):
        assert obs.counter("c") is obs.counter("c")


# ------------------------------------------------------------------- export
def _sample_ring():
    with obs.tracing():
        with obs.span("cluster.run", jobs=3) as sp:
            contracts.record_dispatch("admission.scatter", 2)
            sp.add(retries=1)
        obs.instant("cluster.event_batch", t=1.5, n=4)


class TestExport:
    def test_chrome_trace_round_trip(self, tmp_path):
        _sample_ring()
        path = tmp_path / "trace.perfetto.json"
        n = obs.write_chrome_trace(str(path))
        doc = json.loads(path.read_text())
        assert doc["displayTimeUnit"] == "ms"
        assert len(doc["traceEvents"]) == n == 2
        assert all(ev["pid"] == os.getpid() for ev in doc["traceEvents"])
        back = obs.read_events(str(path))
        assert len(back) == 2
        assert back[0]["dispatches"] == {"admission.scatter": 2}

    def test_jsonl_round_trip(self, tmp_path):
        _sample_ring()
        path = tmp_path / "trace.jsonl"
        n = obs.write_jsonl(str(path))
        back = obs.read_events(str(path))
        assert len(back) == n == 2
        assert [e["name"] for e in back] == [e["name"] for e in obs.events()]

    def test_summarize_reports_span_table(self):
        _sample_ring()
        text = obs.summarize()
        assert "cluster.run" in text
        assert "cluster.event_batch" in text  # loose instants section

    def test_summarize_cli(self, tmp_path, capsys):
        _sample_ring()
        path = tmp_path / "t.jsonl"
        obs.write_jsonl(str(path))
        assert obs_cli(["summarize", str(path)]) == 0
        assert "cluster.run" in capsys.readouterr().out

    def test_prometheus_text_shape(self):
        obs.counter("serve.requests").inc(3, kind="predict")
        obs.gauge("serve.queue_depth").set(7)
        h = obs.hist("serve.wait_s", buckets=(0.001, 0.01))
        h.observe(0.005)
        text = obs.prometheus_text()
        lines = text.splitlines()
        assert 'serve_requests{kind="predict"} 3' in lines
        assert "serve_queue_depth 7" in lines
        assert "# TYPE serve_wait_s histogram" in lines
        assert 'serve_wait_s_bucket{le="+Inf"} 1' in lines
        assert "serve_wait_s_count 1" in lines
        # dotted metric names sanitized for the exposition format
        assert "serve.requests" not in text

    def test_metrics_snapshot_json(self, tmp_path):
        obs.counter("c").inc()
        obs.REGISTRY.series("s").append(0.0, 1.0)
        path = tmp_path / "m.json"
        obs.write_metrics_snapshot(str(path))
        snap = json.loads(path.read_text())
        assert snap["c"]["kind"] == "counter"
        assert snap["s"]["points"] == [[0.0, 1.0]]


# ------------------------------------------------- zero-perturbation contract
def _nodes():
    return [Node(0, 48.0), Node(1, 64.0), Node(2, 32.0)]


def _workload(n_jobs=30, seed=0):
    rng = np.random.default_rng(seed)
    jobs = []
    for j in range(n_jobs):
        L = int(rng.integers(24, 60))
        split = int(rng.uniform(0.4, 0.8) * L)
        lo = float(rng.uniform(1.5, 3.0))
        hi = float(rng.uniform(5.0, 11.0))
        mem = np.concatenate([np.full(split, lo), np.full(L - split, hi)])
        under = rng.uniform() < 0.25
        plan = AllocationPlan(
            starts=np.asarray([0.0, max(split - 2.0, 1.0)]),
            peaks=np.asarray([lo * 1.15, hi * (0.9 if under else 1.12)]))
        jobs.append(Job(jid=j, family="t", input_gb=1.0, mem=mem, dt=1.0,
                        plan=plan, est_runtime=float(L)))
    return jobs


class TestZeroPerturbation:
    def test_traced_replay_bitwise_under_churn(self):
        churn = FaultSchedule.node_churn(_nodes(), rate=1.0 / 120.0,
                                         horizon=600.0, seed=0,
                                         mean_down=60.0)
        base = ClusterSim(_nodes(), engine="fused").run(
            _workload(), RetrySpec("ksplus"), faults=churn)
        assert obs.events() == []  # untraced run records nothing
        traced = ClusterSim(_nodes(), engine="fused").run(
            _workload(), RetrySpec("ksplus"), faults=churn, trace=True)
        assert traced.placements == base.placements
        assert traced.retries == base.retries
        assert traced.evictions == base.evictions
        assert traced.total_wastage_gbs == base.total_wastage_gbs
        assert not obs.trace.enabled  # trace=True is scoped to the run
        names = {e["name"] for e in obs.events()}
        assert "cluster.run" in names and "admission.drain" in names
        # The drain's round trip and the OOM re-plan are spanned.
        assert {"admission.drain.operands", "admission.drain.launch",
                "admission.drain.readback", "cluster.retry"} <= names

    def test_membership_spans_under_churn(self):
        """One ``cluster.leave`` span per leave and one ``cluster.join``
        per join, inside ``cluster.run`` and outside the drains that
        follow them; the leaves' ``evicted`` args add up to the
        replay's evictions, and tracing changes no decision."""
        churn = FaultSchedule.node_churn(_nodes(), rate=1.0 / 120.0,
                                         horizon=600.0, seed=0,
                                         mean_down=60.0)
        base = ClusterSim(_nodes(), engine="fused").run(
            _workload(), RetrySpec("ksplus"), faults=churn)
        assert obs.events() == []
        traced = ClusterSim(_nodes(), engine="fused").run(
            _workload(), RetrySpec("ksplus"), faults=churn, trace=True)
        assert traced.placements == base.placements
        assert (traced.evictions, traced.unschedulable, traced.starved,
                traced.starvation_s, traced.total_wastage_gbs) == (
            base.evictions, base.unschedulable, base.starved,
            base.starvation_s, base.total_wastage_gbs)
        ring = obs.events()

        def iv(e):
            return (e["ts"], e["ts"] + e["dur"])
        for kind in ("leave", "join"):
            got = [e for e in ring if e["name"] == f"cluster.{kind}"]
            assert [e["args"]["nid"] for e in got] == \
                [fe.nid for fe in churn if fe.kind == kind]
        leaves = [e for e in ring if e["name"] == "cluster.leave"]
        assert sum(e["args"]["evicted"] for e in leaves) == \
            base.evictions > 0
        assert sum(e["args"]["doomed"] for e in leaves) <= \
            base.unschedulable
        members = [iv(e) for e in ring
                   if e["name"] in ("cluster.leave", "cluster.join")]
        runs = [iv(e) for e in ring if e["name"] == "cluster.run"]
        drains = [iv(e) for e in ring if e["name"] == "admission.drain"]
        assert all(_inside(m, runs) for m in members)
        assert not any(_inside(d, members) for d in drains)

    def test_traced_run_inside_enabled_scope_not_double_disabled(self):
        jobs = _workload(n_jobs=8)
        with obs.tracing():
            ClusterSim(_nodes(), engine="fused").run(
                jobs, RetrySpec("ksplus"), trace=True)
            assert obs.trace.enabled  # outer scope's switch survives

    def test_traced_serve_plans_bitwise(self):
        from repro.serve.bench import _run_tape, build_server, request_tape

        tape = request_tape(64, tenants=2, seed=3, repeat_pool=16)

        def plans(traced):
            clock = [0.0]
            srv = build_server(tenants=2, clock=lambda: clock[0])
            if traced:
                with obs.tracing():
                    return _run_tape(srv, tape)
            return _run_tape(srv, tape)

        base, traced = plans(False), plans(True)
        assert len(base) == len(traced) == 64
        for a, b in zip(base, traced):
            np.testing.assert_array_equal(a.starts, b.starts)
            np.testing.assert_array_equal(a.peaks, b.peaks)
        assert obs.counter("serve.requests").value(
            kind="predict", cache="miss") > 0


# ------------------------------------------------- the profiler's clock
FIXTURE = os.path.join(os.path.dirname(__file__), "..", "bench", "fixtures",
                       "eager-tiny.xplane.pb.gz")
DRAIN_PARTS = ("admission.drain.operands", "admission.drain.launch",
               "admission.drain.readback")


@pytest.fixture(scope="module")
def profiled_replay(tmp_path_factory):
    """One traced fused churn replay under ``jax.profiler.trace``: the
    ring's events and the program spans read back from the xplane."""
    import glob

    import jax

    from repro.obs import xplane

    churn = FaultSchedule.node_churn(_nodes(), rate=1.0 / 120.0,
                                     horizon=600.0, seed=0, mean_down=60.0)
    ClusterSim(_nodes(), engine="fused").run(   # compile outside the trace
        _workload(), RetrySpec("ksplus"), faults=churn)
    d = str(tmp_path_factory.mktemp("xplane"))
    obs.clear()
    with jax.profiler.trace(d):
        ClusterSim(_nodes(), engine="fused").run(
            _workload(), RetrySpec("ksplus"), faults=churn, trace=True)
    ring = [e for e in obs.events() if e["ph"] == "X"]
    obs.clear()
    (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                        recursive=True)
    return ring, xplane.read(xplane.load(path))["spans"]


def _inside(inner, outers):
    s, e = inner
    return any(a <= s and e <= b for a, b in outers)


class TestProfilerClock:
    def test_every_span_is_on_the_host_plane(self, profiled_replay):
        from collections import Counter

        ring, host = profiled_replay
        assert Counter(e["name"] for e in ring) == \
            Counter(sp[0] for sp in host)
        drains = [(s, e) for n, s, e, _ in host if n == "admission.drain"]
        parts = [(s, e) for n, s, e, _ in host if n in DRAIN_PARTS]
        assert drains and len(parts) == 3 * len(drains)
        assert all(_inside(p, drains) for p in parts)

    def test_drain_parts_tile_within_their_drain(self, profiled_replay):
        ring, _ = profiled_replay

        def iv(e):
            return (e["ts"], e["ts"] + e["dur"])
        drains = [e for e in ring if e["name"] == "admission.drain"]
        for d in drains:
            parts = [e for e in ring if e["name"] in DRAIN_PARTS
                     and _inside(iv(e), [iv(d)])]
            assert sorted(e["name"] for e in parts) == sorted(DRAIN_PARTS)
            assert sum(e["dur"] for e in parts) <= d["dur"]
            # The dispatch tag stays on the drain, outside its parts.
            assert d["dispatches"]["admission.drain"] == 1
            assert not any("admission.drain" in (e.get("dispatches") or {})
                           for e in parts)
        runs = [iv(e) for e in ring if e["name"] == "cluster.run"]
        retries = [iv(e) for e in ring if e["name"] == "cluster.retry"]
        assert retries and all(_inside(r, runs) for r in retries)

    def test_span_left_open_by_an_exception_is_dropped(self):
        with obs.tracing():
            with pytest.raises(RuntimeError):
                with obs.span("outer"):
                    obs.span("inner").__enter__()
                    raise RuntimeError
            with obs.span("after"):
                pass
        assert obs.trace._stack() == []
        assert [e["name"] for e in obs.events()] == ["outer", "after"]


class TestIdleCharging:
    def test_nested_spans_charge_the_innermost(self):
        from repro.obs.xplane import charge_idle

        spans = [("run", 0.0, 100.0), ("drain", 10.0, 50.0),
                 ("drain.readback", 30.0, 50.0)]
        got = charge_idle([(20.0, 40.0)], spans)
        assert got == {"drain": 10.0, "drain.readback": 10.0}

    def test_gap_outside_any_span(self):
        from repro.obs.xplane import OUTSIDE, charge_idle

        got = charge_idle([(0.0, 5.0), (120.0, 130.0)],
                          [("run", 10.0, 100.0)])
        assert got == {OUTSIDE: 15.0}

    def test_gap_straddling_two_spans(self):
        from repro.obs.xplane import OUTSIDE, charge_idle

        spans = [("a", 0.0, 10.0), ("b", 12.0, 20.0)]
        got = charge_idle([(5.0, 15.0)], spans)
        assert got == {"a": 5.0, OUTSIDE: 2.0, "b": 3.0}
        assert sum(got.values()) == 10.0

    def test_gaps_and_self_time(self):
        from repro.obs.xplane import gaps, span_rows

        assert gaps([(2.0, 4.0), (3.0, 6.0), (8.0, 9.0)], 0.0, 10.0) == \
            [(0.0, 2.0), (6.0, 8.0), (9.0, 10.0)]
        rows = span_rows([("run", 0.0, 100.0, 1), ("drain", 10.0, 50.0, 1),
                          ("drain.launch", 20.0, 25.0, 1),
                          ("drain", 60.0, 70.0, 1), ("run", 5.0, 8.0, 2)])
        assert rows["run"] == {"n": 2, "total": 103.0, "self": 53.0}
        assert rows["drain"] == {"n": 2, "total": 50.0, "self": 45.0}
        assert rows["drain.launch"]["self"] == 5.0

    def test_summarize_xplane_on_recorded_trace(self, capsys):
        """The recorded v5e trace holds no program span (only the
        harness's unmarked ``bench.replay``): all idle time lands
        outside any span, and the rows add up to the trace's idle."""
        from repro.obs.xplane import OUTSIDE, load, read

        assert obs_cli(["summarize", "--xplane", FIXTURE]) == 0
        out = capsys.readouterr().out
        assert "(no program spans on the host plane)" in out
        (row,) = [ln for ln in out.splitlines() if ln.startswith(OUTSIDE)]
        charged = float(row.split()[-1])
        total = out.splitlines()[-1]
        assert f"idle {charged:.3f} ms" in total
        assert f"charged {charged:.3f} ms" in total
        assert charged > 0
        assert read(load(FIXTURE))["spans"] == []

    def test_summarize_needs_one_input(self, capsys):
        with pytest.raises(SystemExit):
            obs_cli(["summarize"])
