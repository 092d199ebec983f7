"""Runtime dispatch/compile contracts (``repro.analysis.contracts``).

The engine's performance story rests on three invariants that every
differential test is blind to — placements stay bitwise-identical
whether the engine compiles one program or one per event.  This suite
makes them fail loudly instead:

* **one program per drain** — a queue within ``DRAIN_CAP`` dispatches
  whole: one ``admission.drain`` dispatch per ``drain()`` call and zero
  new compiles once the pow2 buckets are warm;
* **bounded compiled-shape count under bucket routing** — a 200-task
  DAG replay whose dependency frontier wanders stays within a fixed
  compile budget, and a second replay with a different seed compiles
  NOTHING new (every frontier size lands in an already-compiled pow2/
  pow4 bucket);
* **zero rebuild on churn** — node join/leave never re-uploads the
  device-resident lane state; ``admission.dev_sync`` fires exactly once
  per replay (the initial upload).

Mechanics: compiles are counted through jax's monitoring hook (fires
once per backend compilation, never on a cache hit); dispatches are
self-reported by the engine's call sites via ``record_dispatch``.
"""

import numpy as np
import pytest

from repro.analysis.contracts import (Budget, DispatchBudgetError,
                                      dispatch_budget, record_dispatch)
from repro.core import RetrySpec
from repro.sched import ClusterSim, ElasticPlanner, FaultSchedule

from test_admission_fused import _mk_lanes, _mk_state, _storm_env
from test_cluster_packed import _nodes, _workload
from test_faults import _workload as _timed_workload


# ------------------------------------------------------- budget mechanics
class TestDispatchBudgetUnit:
    def test_compile_counting_and_cache_hits(self):
        import jax
        import jax.numpy as jnp
        jnp.ones(16).block_until_ready()  # warm implicit constructors

        fn = jax.jit(lambda x: x * 3 + 1)
        with dispatch_budget() as cold:
            fn(jnp.ones(16)).block_until_ready()
        assert cold.compiles == 1
        with dispatch_budget(compiles=0) as warm:
            fn(jnp.ones(16)).block_until_ready()
        assert warm.compiles == 0

    def test_compile_budget_violation_raises(self):
        import jax
        import jax.numpy as jnp
        with pytest.raises(DispatchBudgetError, match="compiled"):
            with dispatch_budget(compiles=0):
                jax.jit(lambda x: x - 7)(jnp.ones(4)).block_until_ready()

    def test_dispatch_tags_and_forbid(self):
        record_dispatch("t.outside")  # before the scope: not counted
        with dispatch_budget(dispatches=3, tags=("t.a",)) as b:
            record_dispatch("t.a", 2)
            record_dispatch("t.b", 5)  # untagged for this budget
        assert b.tag_counts["t.a"] == 2
        assert b.tag_counts["t.b"] == 5
        assert b.dispatches == 2
        with pytest.raises(DispatchBudgetError, match="forbidden"):
            with dispatch_budget(forbid=("t.boom",)):
                record_dispatch("t.boom")

    def test_dispatch_ceiling_violation(self):
        with pytest.raises(DispatchBudgetError, match="launched"):
            with dispatch_budget(dispatches=1):
                record_dispatch("t.c", 2)

    def test_budget_readable_after_exit(self):
        with dispatch_budget() as b:
            record_dispatch("t.after", 4)
        assert isinstance(b, Budget)
        assert b.tag_counts["t.after"] == 4
        assert b.violations() == []


# -------------------------------------------------- one program per drain
class TestOneProgramPerDrain:
    @staticmethod
    def _scripted_drains(seed=8, caps=(40.0, 20.0, 36.0)):
        """Deterministic drain sequence: admit 14 lanes, drain three
        times with a release in between — walks the empty AND occupied
        pow4 resident buckets."""
        adm = _mk_state("fused", caps=caps)
        lanes = _mk_lanes(adm, np.random.default_rng(seed), 14)
        placed = adm.drain(0.0, lanes)
        if placed:
            ji, ni = placed[0]
            adm.release(ni, ji)
        adm.drain(7.0, lanes)
        adm.drain(40.0, lanes)
        return adm

    def test_warm_drains_compile_nothing(self):
        """A second identically-shaped drain sequence on a FRESH state
        reuses every cached while-loop program: zero new compiles,
        exactly one ``admission.drain`` dispatch per ``drain()`` call.
        Values (caps, `now`, residency) change between the drains inside
        the scope; shapes are what the bucket routing must keep stable."""
        self._scripted_drains()  # warm every pow2/pow4 bucket the script hits
        with dispatch_budget(compiles=0) as b:
            adm = self._scripted_drains()
        assert b.tag_counts["admission.drain"] == 3
        assert adm.stats["drain_dispatches"] == adm.stats["drains"] == 3

    def test_one_upload_per_drain_no_scalar_converts(self, monkeypatch):
        """The drain's per-call operands cross to the device as ONE
        packed buffer: exactly one ``admission.drain.upload`` per
        ``drain()`` call on a warm scripted run, and no eager
        ``convert_element_type`` program (what ``jnp.float64`` scalar
        operands launch) dispatched or compiled in the scope."""
        import jax
        import jax.numpy as jnp
        from jax._src import dispatch

        self._scripted_drains()  # warm every bucket the script hits
        prims = []
        eager = dispatch.xla_primitive_callable

        def spy(prim, **params):
            prims.append(prim.name)
            return eager(prim, **params)

        monkeypatch.setattr(dispatch, "xla_primitive_callable", spy)
        with dispatch_budget(compiles=0) as b:
            adm = self._scripted_drains()
        assert b.tag_counts["admission.drain.upload"] == 3
        assert adm.stats["drains"] == 3
        assert b.tag_counts["admission.drain"] == 3
        assert "convert_element_type" not in prims
        # The spy sees the pattern it guards against.
        with jax.enable_x64(True):
            jnp.float64(1.5)
        assert "convert_element_type" in prims

    @staticmethod
    def _scripted_run(seed=8, caps=(40.0, 20.0, 36.0)):
        """Residents placed at t=0, a standing queue, then a same-instant
        run of 4 completions at t=7 (:meth:`AdmissionState.drain_run`)."""
        adm = _mk_state("fused", caps=caps)
        lanes = _mk_lanes(adm, np.random.default_rng(seed), 14)
        placed = adm.drain(0.0, lanes)
        got = {ji for ji, _ in placed}
        queue = [ji for ji in lanes if ji not in got]
        return adm, queue, [(ni, ji, [], None) for ji, ni in placed[:4]]

    def test_same_instant_run_is_one_program(self):
        """A run of 4 completions drains in ONE program: one
        ``admission.drain`` dispatch, one ``admission.drain.upload`` and
        no compile once its shapes are warm."""
        adm, queue, events = self._scripted_run()
        adm.drain_run(7.0, queue, events)  # warm the run's shapes
        adm, queue, events = self._scripted_run()
        assert len(events) == 4 and queue
        with dispatch_budget(compiles=0) as b:
            placed = adm.drain_run(7.0, queue, events)
        assert b.tag_counts["admission.drain"] == 1
        assert b.tag_counts["admission.drain.upload"] == 1
        assert len(placed) == 4 and any(placed)

    def test_different_caps_same_program(self):
        """Capacity values are operands, not shapes: once each scripted
        config has warmed its buckets, fresh states under either config
        compile nothing new."""
        self._scripted_drains(caps=(40.0, 20.0, 36.0))
        self._scripted_drains(caps=(24.0, 64.0, 18.0))
        with dispatch_budget(compiles=0) as b:
            self._scripted_drains(caps=(40.0, 20.0, 36.0))
            self._scripted_drains(caps=(24.0, 64.0, 18.0))
        assert b.compiles == 0
        assert b.tag_counts["admission.drain"] == 6

    def test_elastic_drain_shares_program(self):
        """ElasticPlanner's fused drain rides the same compiled program
        family; a scripted submit/churn run stays one dispatch per
        drain with no recompiles once warm."""
        def run(seed):
            rng = np.random.default_rng(seed)
            pl = ElasticPlanner(backend="fused")
            pl.node_join("n0", 48.0)
            pl.node_join("n1", 32.0)
            for step in range(12):
                pl.submit(f"j{step}",
                          _storm_env(rng, float(rng.uniform(6, 24))),
                          float(step))
            pl.drain(20.0)
            return pl

        run(0)  # warm every bucket this script reaches
        with dispatch_budget(compiles=0) as b:
            pl = run(0)  # same script, fresh planner: all shapes cached
        assert b.tag_counts["admission.drain"] >= 1
        del pl


# ------------------------------------- bounded shapes while frontier wanders
class TestBoundedShapesUnderWander:
    # Measured cold on jax 0.9.0 CPU: 23 compiles for the full replay
    # (drain program per queue bucket + columns + scatter + probe).  The
    # bound is deliberately loose — without pow2/pow4 bucketing the
    # wandering frontier compiles per distinct size and blows through it
    # by an order of magnitude.
    COLD_COMPILE_BUDGET = 40

    def _replay(self, seed, engine="fused"):
        from repro.workloads import scenarios
        wf = scenarios.get("workload_replay", n_tasks=200, seed=seed)
        sim = ClusterSim(_nodes(), engine=engine, drain="device")
        return sim.run(wf.to_jobs(under_frac=0.2, seed=seed),
                       RetrySpec("ksplus"))

    def test_dag_frontier_compiles_stay_bucketed(self):
        with dispatch_budget(compiles=self.COLD_COMPILE_BUDGET) as cold:
            self._replay(seed=0)
        assert cold.tag_counts["admission.drain"] > 50  # frontier wandered
        # The attempt-#1 probe compiles once per trace-length bucket, a
        # property of the workload and not of the frontier: seed 0 pads
        # its traces to 256 samples, seed 3 to 128.  The packed engine
        # runs the same set-up probe but admits on the host, so it warms
        # seed 3's bucket without touching a drain program.
        with dispatch_budget() as probe_warmup:
            self._replay(seed=3, engine="packed")
        assert probe_warmup.tag_counts["admission.drain"] == 0
        # A different workload, same scenario family: every frontier
        # size lands in an already-compiled bucket.
        with dispatch_budget(compiles=0) as warm:
            self._replay(seed=3)
        assert warm.tag_counts["admission.drain"] > 50
        assert warm.compiles == 0


# --------------------------------------------------- zero rebuild on churn
class TestZeroRebuildOnChurn:
    def test_node_churn_never_resyncs_device_state(self):
        """Joins and leaves only change the next dispatch's operands;
        the packed lane buffers upload exactly once per replay."""
        faults = FaultSchedule.node_churn(_nodes(), rate=0.04,
                                          horizon=250.0, seed=5)
        sim = ClusterSim(_nodes(), engine="fused", drain="device")
        with dispatch_budget() as b:
            res = sim.run(_timed_workload(48, seed=5, under_frac=0.4),
                          RetrySpec("ksplus"), faults=faults)
        assert res.evictions > 0  # churn actually happened
        assert b.tag_counts["admission.dev_sync"] == 1
        assert b.tag_counts["admission.drain"] >= res.evictions // 2

    def test_storm_rejoin_no_rebuild(self):
        faults = FaultSchedule.preemption_storm(
            _nodes(), t=30.0, frac=0.9, seed=2, down_time=35.0)
        sim = ClusterSim(_nodes(), engine="fused", drain="device")
        with dispatch_budget(forbid=()) as b:
            res = sim.run(_timed_workload(40, seed=3, under_frac=0.5),
                          RetrySpec("ksplus"), faults=faults)
        assert res.evictions > 0
        assert b.tag_counts["admission.dev_sync"] == 1


# ------------------------------------------------------- serving contracts
class TestServeContracts:
    """The serving path's dispatch discipline (see repro.serve):

    * one ``serve.batch`` dispatch per bucket flush,
    * zero compiles on warm traffic (pow2 lane padding + per-snapshot
      trace residency bound the shape set),
    * ``serve.dev_sync`` fires once per (tenant, family, snapshot) and
      never again until a refit forks the snapshot.
    """

    def _warm_server(self, tenants=2):
        from repro.serve.bench import FAMILIES, build_server, request_tape

        srv = build_server(tenants=tenants, batching=True, max_batch=64,
                           seed=0)
        futs = [srv.submit("predict", t, f, x)
                for t, f, x in request_tape(128, tenants, seed=1)]
        srv.drain()
        [f.result(0) for f in futs]
        for t in range(tenants):
            client = srv.client(f"tenant{t}")
            for family, _ in FAMILIES:
                client.evaluate(family)
        srv.client("tenant0").tune_offset("align")
        return srv

    def test_warm_serve_zero_compiles_one_batch_per_bucket(self):
        from repro.serve.bench import FAMILIES, request_tape

        srv = self._warm_server()
        before = srv._batcher.stats["batches"]
        with dispatch_budget(compiles=0,
                             forbid=("serve.dev_sync",)) as warm:
            futs = [srv.submit("predict", t, f, x)
                    for t, f, x in request_tape(96, 2, seed=7)]
            srv.drain()
            [f.result(0) for f in futs]
            for t in range(2):
                client = srv.client(f"tenant{t}")
                for family, _ in FAMILIES:
                    client.evaluate(family)
            srv.client("tenant0").tune_offset("align")
        flushed_buckets = srv._batcher.stats["batches"] - before
        # exactly one serve.batch dispatch per bucket flush, nothing else
        assert warm.tag_counts["serve.batch"] == flushed_buckets
        assert warm.compiles == 0

    def test_dev_sync_once_per_snapshot_then_refit_scoped(self):
        import numpy as np

        from repro.core.predictor import ExecutionOutcome
        from repro.serve.bench import build_server

        srv = build_server(tenants=2, batching=True, seed=0)
        client = srv.client("tenant0")
        with dispatch_budget() as b:
            client.evaluate("align")
            client.evaluate("align")          # warm: resident traces
            srv.client("tenant1").evaluate("align")  # own (tenant, sid) key
        assert b.tag_counts["serve.dev_sync"] == 2
        client.observe("align", ExecutionOutcome(
            mem=np.full(40, 9.0), dt=1.0, input_gb=3.0, succeeded=True))
        assert client.refit("align")
        with dispatch_budget() as after:
            client.evaluate("align")          # forked sid: one new upload
            client.evaluate("align")
            srv.client("tenant1").evaluate("align")  # old sid: still warm
        assert after.tag_counts["serve.dev_sync"] == 1

    def test_cache_hit_tag_fires_on_submit_fast_path(self):
        from repro.serve.bench import build_server

        srv = build_server(tenants=1, batching=True, seed=0)
        client = srv.client("tenant0")
        client.predict("align", 2.0)
        with dispatch_budget() as b:
            assert client.predict("align", 2.0) is not None
        assert b.tag_counts["serve.cache_hit"] == 1
        assert b.tag_counts.get("serve.batch", 0) == 0  # no dispatch at all
