"""Device-resident drain: differential + dispatch-accounting coverage.

The fused engine's default ``drain="device"`` path folds the whole
greedy admission loop — fits refresh, (queue, node)-order argmax,
residual scatter, repeat — into ONE jitted dispatch per event
(:meth:`repro.sched.admission.AdmissionState.drain`).  This suite pins
it three ways:

* ``AdmissionState.drain`` unit level — fused placements must equal the
  numpy host drain *bitwise* for both node-selection rules
  (``"first"``/``"headroom"``), with and without durations, across
  repeated drains, and the post-drain fits cache must stay
  oracle-fresh;
* engine level — ``ClusterSim(drain="device")`` must reproduce the host
  fused drain's decision log bitwise (and the legacy engine's wastage to
  1e-6) under DAG replay, churn/storm fault schedules, offset sweeps,
  parking/starvation, and joins landing mid-drain;
* scaling level — a ≥2-shard ``shard_map`` drain (subprocess with forced
  host devices, same idiom as ``test_moe_distributed``) must match the
  unsharded device drain and the numpy drain decision-for-decision.

Dispatch accounting rides along: ``AdmissionState.stats`` must report
exactly one dispatch per device drain — the tentpole's whole point.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import AllocationPlan, RetrySpec
from repro.sched import (
    ClusterSim,
    ElasticPlanner,
    FaultEvent,
    FaultSchedule,
    Job,
    Node,
    OffsetCandidate,
)
from repro.analysis.contracts import dispatch_budget
from repro.sched.admission import (
    RUN_STEPS,
    AdmissionState,
    _drain_pack,
    _drain_unpack,
)

from test_admission_fused import (
    _assert_same,
    _mk_lanes,
    _mk_state,
    _scratch_fits,
    _storm_env,
)
from test_cluster_packed import _nodes, _workload
from test_faults import _workload as _timed_workload


def _host_sim(**kw):
    return ClusterSim(_nodes(), engine="fused", drain="host", **kw)


def _dev_sim(**kw):
    return ClusterSim(_nodes(), engine="fused", drain="device", **kw)


# ------------------------------------------------------------- unit level
class TestDrainUnit:
    @pytest.mark.parametrize("select", ["first", "headroom"])
    @pytest.mark.parametrize("use_dur", [True, False])
    def test_fused_matches_numpy_host_drain(self, select, use_dur):
        rng = np.random.default_rng(0)
        out = {}
        for backend in ("numpy", "fused"):
            r = np.random.default_rng(7)
            adm = _mk_state(backend, caps=(32.0, 48.0, 24.0),
                            use_dur=use_dur)
            lanes = _mk_lanes(adm, r, 14)
            out[backend] = adm.drain(3.0, lanes, select=select)
        assert out["fused"] == out["numpy"]
        assert len(out["fused"]) > 0
        del rng

    def test_repeated_drains_and_cache_coherence(self):
        """Drain, mutate residency, drain again — the device path must
        keep agreeing with the host drain AND leave the shared fits
        cache in a state the invalidation protocol can serve fresh."""
        states = {}
        for backend in ("numpy", "fused"):
            rng = np.random.default_rng(11)
            adm = _mk_state(backend, caps=(24.0, 40.0))
            lanes = _mk_lanes(adm, rng, 16)
            states[backend] = (adm, list(lanes))
        placed0 = {}
        for backend, (adm, lanes) in states.items():
            placed0[backend] = adm.drain(0.0, lanes)
        assert placed0["fused"] == placed0["numpy"]
        done = {ji for ji, _ in placed0["fused"]}
        rest = [ji for ji in states["fused"][1] if ji not in done]
        for backend, (adm, _) in states.items():
            # release one resident, advance time, drain the remainder
            ji, ni = placed0[backend][0]
            adm.release(ni, ji)
            placed0[backend] = adm.drain(9.0, rest + [ji])
        assert placed0["fused"] == placed0["numpy"]
        adm, lanes = states["fused"]
        np.testing.assert_array_equal(
            adm.columns(9.0, lanes), _scratch_fits(adm, 9.0, lanes))

    def test_one_dispatch_per_drain(self):
        rng = np.random.default_rng(3)
        adm = _mk_state("fused")
        lanes = _mk_lanes(adm, rng, 12)
        remaining = list(lanes)
        for now in (0.0, 5.0, 50.0):
            placed = adm.drain(now, remaining)
            done = {ji for ji, _ in placed}
            remaining = [ji for ji in remaining if ji not in done]
        assert adm.stats["drains"] == 3
        # Queues within DRAIN_CAP go straight into the program, whole:
        # exactly ONE dispatch per drain, multi-placement or empty.
        assert adm.stats["drain_dispatches"] == adm.stats["drains"]

    def test_wide_queue_prefilter_caps_dispatch(self):
        # Above DRAIN_CAP the drain pre-filters candidates through the
        # cached columns and dispatches at most the cap; placements
        # must still match the host oracle exactly.
        rng = np.random.default_rng(9)
        adm = _mk_state("fused")
        ref = _mk_state("fused")
        old_cap = type(adm).DRAIN_CAP
        lanes = _mk_lanes(adm, rng, 48)
        _mk_lanes(ref, np.random.default_rng(9), 48)
        try:
            type(adm).DRAIN_CAP = 16  # force the wide path on `adm`
            got = adm.drain(0.0, lanes)
        finally:
            type(adm).DRAIN_CAP = old_cap
        assert got == ref.drain(0.0, lanes)
        assert got  # the scenario actually places

    def test_select_validation(self):
        adm = _mk_state("fused")
        with pytest.raises(ValueError, match="select"):
            adm.drain(0.0, [], select="best")

    def test_shard_requires_fused_backend(self):
        with pytest.raises(ValueError, match="shard"):
            AdmissionState([32.0], K=2, G=8, backend="numpy", shard=2)

    def test_shard_requires_devices(self):
        import jax
        n = jax.device_count()
        with pytest.raises(ValueError, match="device"):
            AdmissionState([32.0], K=2, G=8, backend="fused", shard=n + 1)


# -------------------------------------------------------- packed operands
class TestPackedOperands:
    """The unsharded drain carries its per-call operands in ONE float64
    vector (``_drain_pack``) that the program slices apart
    (``_drain_unpack``): every field must come back bit for bit."""

    @pytest.mark.parametrize("now,tol", [(0.1 + 0.2, 1e-9),
                                         (98765.43210987654, 1e-9),
                                         (0.0, 1e-12)])
    def test_pack_unpack_bitwise(self, now, tol):
        import jax
        rng = np.random.default_rng(31)
        B, N, npad, rmax, nq, Q, K = 8192, 5, 8, 16, 37, 64, 4
        S = RUN_STEPS
        caps = np.full(npad, -1e30)
        caps[:N] = rng.uniform(16.0, 128.0, N)
        node_valid = np.arange(npad) < N
        run_idx = rng.integers(0, B, (npad, rmax)).astype(np.int32)
        run_idx[0, 0] = B - 1
        run_valid = rng.uniform(size=(npad, rmax)) < 0.5
        used_now = np.where(node_valid, rng.uniform(0.0, 64.0, (S, npad)),
                            np.nan)
        q_idx = np.zeros(Q, np.int32)
        q_idx[:nq] = rng.integers(0, B + 1, nq)
        q_idx[nq - 1] = B
        q_from = np.full(Q, S, np.int32)
        q_from[:nq] = rng.integers(0, S, nq)
        leave_node = rng.integers(0, npad + 1, S).astype(np.int32)
        leave_slot = rng.integers(0, rmax, S).astype(np.int32)
        old_starts = rng.uniform(0.0, 1e3, (S, K))
        old_starts[0, -1] = 1e30
        old_peaks = rng.uniform(0.0, 128.0, (S, K))
        want = (caps, node_valid, run_idx, run_valid, used_now, q_idx,
                q_from, leave_node, leave_slot, old_starts, old_peaks,
                np.int32(S - 3), np.float64(now), np.float64(tol))
        packed = _drain_pack(*want)
        assert packed.dtype == np.float64
        assert packed.shape == (2 * npad + 2 * npad * rmax + S * npad
                                + 2 * Q + 2 * S + 2 * S * K + 3,)
        unpack = jax.jit(_drain_unpack, static_argnums=(1, 2, 3, 4))
        with jax.enable_x64(True):
            got = jax.device_get(
                unpack(jax.device_put(packed), npad, rmax, Q, K))
        for w, g in zip(want, got):
            g = np.asarray(g)
            assert (g.dtype, g.shape) == (w.dtype, w.shape)
            assert g.tobytes() == w.tobytes()

    @pytest.mark.parametrize("select", ["first", "headroom"])
    def test_q256_bucket_matches_numpy_host_drain(self, select):
        """A queue in the largest bucket the cohort cells reach (Q=256)
        through the packed program: one upload, one dispatch, and the
        numpy host drain's placements."""
        caps = (96.0, 128.0, 64.0, 112.0, 80.0, 128.0, 72.0, 104.0)
        out = {}
        for backend in ("numpy", "fused"):
            adm = _mk_state(backend, caps=caps)
            lanes = _mk_lanes(adm, np.random.default_rng(17), 200)
            with dispatch_budget() as b:
                out[backend] = adm.drain(5.0, lanes, select=select)
        assert b.tag_counts["admission.drain.upload"] == 1
        assert adm.stats["drain_dispatches"] == 1
        assert out["fused"] == out["numpy"]
        assert 8 < len(out["fused"]) < 200


# ------------------------------------------------ the admission instant
class TestAdmissionInstantFromHost:
    """At the first grid point, ``now`` itself, a resident's ``now - t0``
    can lie exactly on a segment start, and a TPU's emulated float64
    (about 49 bits) can round it to the other segment than the host.
    The device programs take that point from the host, so a device copy
    of ``t0`` a few ulps off, as a TPU holds it, changes no decision."""

    T0, NOW, START = 0.1, 5.1, 5.0

    def _state(self, backend):
        adm = AdmissionState([10.0], K=2, G=8, backend=backend)
        grid = np.linspace(0.0, 50.0, 8)[None].repeat(2, axis=0)
        need = np.ones((2, 8))
        need[1, 0] = 3.0      # fits at ``now`` only beside segment 0
        r, q = adm.add_lanes(np.asarray([[0.0, self.START], [0.0, 1e30]]),
                             np.asarray([[2.0, 8.0], [3.0, 3.0]]), need,
                             grid, dur=np.asarray([100.0, 100.0]))
        adm.place(0, int(r), self.T0)
        return adm, int(r), int(q)

    def _nudged(self, adm, r):
        """The device's ``t0`` moved 1e-12 across the tie, the other way
        from where the host's rounding put ``now - t0``."""
        import jax
        rel = self.NOW - self.T0
        t0 = self.T0 + (-1e-12 if rel < self.START else 1e-12)
        with jax.enable_x64(True):
            adm._dev_sync()
            adm._dadmit = adm._dadmit.at[r].set(t0)

    def test_drain_and_columns_follow_the_host(self):
        host, r, q = self._state("numpy")
        want = host.drain(self.NOW, [q])
        want_fits = self._state("numpy")[0].columns(self.NOW, [q])
        adm, r, q = self._state("fused")
        self._nudged(adm, r)
        assert adm.drain(self.NOW, [q]) == want
        adm, r, q = self._state("fused")
        self._nudged(adm, r)
        assert (adm.columns(self.NOW, [q]) == want_fits).all()

    def test_whole_instants_keep_the_device_value(self):
        """While every instant is a whole number the device's float64 is
        exact at ``now``, and the host sends nothing to replace it; the
        first fractional instant turns the host's value on for good."""
        adm, r, q = self._state("fused")
        assert not adm._whole_times          # placed at T0 = 0.1
        whole = AdmissionState([10.0], K=2, G=8, backend="fused")
        whole.add_lanes(np.asarray([[0.0, 5.0]]), np.asarray([[2.0, 8.0]]),
                        np.ones((1, 8)), np.linspace(0.0, 50.0, 8)[None],
                        dur=np.asarray([100.0]))
        whole.place(0, 0, 1.0)
        whole.sync_now(6.0)
        assert np.isnan(whole._used_now(whole.running, 6.0, 2)).all()
        whole.sync_now(6.5)
        assert whole._used_now(whole.running, 6.5, 2).tolist() == [8.0, 0.0]
        whole.sync_now(7.0)
        assert not whole._whole_times

    def test_grid_must_start_at_the_instant(self):
        adm = AdmissionState([10.0], K=2, G=4, backend="numpy")
        with pytest.raises(ValueError, match="start at 0"):
            adm.add_lanes(np.zeros((1, 2)), np.ones((1, 2)), np.ones((1, 4)),
                          np.linspace(1.0, 4.0, 4)[None])


# ------------------------------------------------- a same-instant run
def _run_case(backend, case, select, drain_cap=None):
    """A standing queue on four nodes at ``t0``, then a same-instant run
    of events at ``now``: residents leave in placement order, fresh lanes
    join (``children``), retried residents re-join with another re-plan
    (``retry``), the queue starts empty (``empty``), the run is longer
    than one program call (``long``), instants carry fractions and lie a
    whole number of samples from whole-number segment starts
    (``fractional``), the queue is wider than ``drain_cap`` (``wide``,
    which goes event by event).  Returns ``(adm, now, queue, events)``."""
    from repro.core.envelope import alloc_at_packed
    seed = {"retry": 1, "children": 2, "empty": 3, "long": 4,
            "fractional": 5, "wide": 6}[case]
    adm = _mk_state(backend, caps=(96.0, 64.0, 120.0, 80.0))
    if drain_cap is not None:
        adm.DRAIN_CAP = drain_cap
    lanes = [int(x) for x in _mk_lanes(adm, np.random.default_rng(seed), 90)]
    t0, now = (0.5, 12.5) if case == "fractional" else (0.0, 12.0)
    if case == "fractional":
        for ji in lanes:
            st = np.where(adm.starts[ji] < 1e29, np.round(adm.starts[ji]),
                          adm.starts[ji])
            adm.update_lane(ji, st, adm.peaks[ji], alloc_at_packed(
                st[None], adm.peaks[ji][None], adm.grid[ji][None])[0])
    pre, children = lanes[:55], lanes[55:]
    residents = adm.drain(t0, pre, select=select)
    placed0 = {ji for ji, _ in residents}
    queue = [] if case == "empty" else [ji for ji in pre
                                        if ji not in placed0]
    if case == "retry":  # a short queue, so re-queued retries can place
        queue, children = queue[:3], []
    n = RUN_STEPS + 4 if case == "long" else 7
    # Retried mid-run: the first two residents after the first event
    # whose envelope has more than one segment.
    multi = [k for k, (ji, _) in enumerate(residents[:n])
             if k and (adm.starts[ji] < 1e29).sum() > 1][:2]
    events = []
    for k, (ji, ni) in enumerate(residents[:n]):
        joins = (children[2 * k:2 * k + 2]
                 if case != "empty" or k >= 2 else [])
        replan = None
        if case == "retry" and k in multi:
            # One re-plan moves the segment starts, the other the peaks.
            first = k == multi[0]
            st = np.where(adm.starts[ji] < 1e29, adm.starts[ji]
                          * (0.1 if first else 1.0), adm.starts[ji])
            peaks = adm.peaks[ji] * (1.0 if first else 0.3)
            replan = (st, peaks, alloc_at_packed(
                st[None], peaks[None], adm.grid[ji][None])[0])
            joins = joins + [ji]
        events.append((ni, ji, joins, replan))
    return adm, now, queue, events


def _one_by_one(adm, now, queue, events, select):
    """The events drained one at a time, as the per-event loop did."""
    out, q = [], list(queue)
    for ni, ji, joins, replan in events:
        adm.release(ni, ji)
        if replan is not None:
            adm.update_lane(ji, *replan)
        q += joins
        got = adm.drain(now, q, select=select) if q else []
        out.append(got)
        q = [x for x in q if x not in {lane for lane, _ in got}]
    return out


class TestDrainRun:
    """``AdmissionState.drain_run`` folds a same-instant run of events
    into one program call per ``RUN_STEPS`` events; its placements, their
    order and the residents it leaves must be bitwise those of the same
    events drained one by one, and the numpy host drain's."""

    @pytest.mark.parametrize("select", ["first", "headroom"])
    @pytest.mark.parametrize("case", ["retry", "children", "empty", "long",
                                      "fractional", "wide"])
    def test_matches_events_one_by_one(self, case, select):
        cap = 8 if case == "wide" else None
        adm, now, queue, events = _run_case("fused", case, select, cap)
        d0 = adm.stats["drain_dispatches"]
        with dispatch_budget() as b:
            got = adm.drain_run(now, queue, events, select=select)
        calls = adm.stats["drain_dispatches"] - d0
        tags = b.tag_counts
        ref, rnow, rqueue, revents = _run_case("fused", case, select, cap)
        want = _one_by_one(ref, rnow, rqueue, revents, select)
        host, hnow, hqueue, hevents = _run_case("numpy", case, select, cap)
        assert got == want
        assert host.drain_run(hnow, hqueue, hevents, select) == want
        assert adm.running == ref.running == host.running
        assert len(got) == len(events)
        # Placements happen after the first event, not only at step 0.
        assert sum(len(p) for p in got[1:]) > 0
        if case == "wide":
            assert calls == tags["admission.drain"] > 1
        else:
            assert calls == tags["admission.drain"] \
                == tags["admission.drain.upload"] \
                == -(-len(events) // RUN_STEPS)
        if case == "empty":
            assert got[0] == got[1] == []
        if case == "fractional":
            assert not adm._whole_times
        if case == "retry":
            retried = {ji for _, ji, _, rp in events if rp is not None}
            assert retried & {ji for p in got for ji, _ in p}

    @pytest.mark.parametrize("select", ["first", "headroom"])
    @pytest.mark.parametrize("moved", ["starts", "peaks"])
    def test_retried_resident_keeps_its_admitted_envelope(self, moved,
                                                          select):
        """One node of 10: X leaves at step 0, then R is retried at step
        1 with a re-plan of 9 from ``rel`` 1 on (``moved``: a segment
        start brought in, or the peaks raised).  Until it leaves, R
        counts with the 2 it was admitted with, so the queued L (4)
        places at step 0, not step 1."""
        grid = np.linspace(0.0, 8.0, 8)
        old = (np.asarray([0.0, 100.0]), np.asarray([2.0, 9.0])) \
            if moved == "starts" else \
            (np.asarray([0.0, 1e30]), np.asarray([2.0, 2.0]))
        new = (np.asarray([0.0, 1.0]), np.asarray([2.0, 9.0])) \
            if moved == "starts" else \
            (np.asarray([0.0, 1e30]), np.asarray([9.0, 9.0]))
        from repro.core.envelope import alloc_at_packed

        def need(st, pk):
            return alloc_at_packed(st[None], pk[None], grid[None])[0]

        def build(backend):
            adm = AdmissionState([10.0], K=2, G=8, backend=backend)
            one = np.asarray([0.0, 1e30])
            adm.add_lanes(np.stack([one, old[0], one]),
                          np.stack([np.full(2, 5.0), old[1], np.full(2, 4.0)]),
                          np.stack([need(one, np.full(2, 5.0)), need(*old),
                                    need(one, np.full(2, 4.0))]),
                          np.stack([grid] * 3))
            adm.place(0, 0, 0.0)     # X
            adm.place(0, 1, 0.0)     # R
            return adm

        events = [(0, 0, [], None), (0, 1, [1], (*new, need(*new)))]
        want = [[(2, 0)], []]
        assert _one_by_one(build("fused"), 3.0, [2], events, select) == want
        for backend in ("numpy", "fused"):
            got = build(backend).drain_run(3.0, [2], events, select=select)
            assert got == want, backend

    def test_steps_counted(self):
        adm, now, queue, events = _run_case("fused", "long", "first")
        s0 = dict(adm.stats)
        adm.drain_run(now, queue, events)
        assert adm.stats["drain_steps"] - s0["drain_steps"] == len(events)
        assert adm.stats["drains"] - s0["drains"] == 2


# ----------------------------------------------------------- engine level
class TestDeviceDrainDifferential:
    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_matches_host_drain(self, seed):
        host = _host_sim().run(_workload(48, seed=seed), RetrySpec("ksplus"))
        dev = _dev_sim().run(_workload(48, seed=seed), RetrySpec("ksplus"))
        assert host.retries > 0
        _assert_same(dev, host)

    def test_retry_storm(self):
        host = _host_sim().run(_workload(64, seed=11, under_frac=0.8),
                               RetrySpec("ksplus"))
        dev = _dev_sim().run(_workload(64, seed=11, under_frac=0.8),
                             RetrySpec("ksplus"))
        assert host.retries >= 20
        _assert_same(dev, host)

    def test_wastage_vs_legacy(self):
        from repro.core import ksplus_retry
        legacy = ClusterSim(_nodes(), engine="legacy").run(
            _workload(40, seed=1), ksplus_retry)
        dev = _dev_sim().run(_workload(40, seed=1), RetrySpec("ksplus"))
        assert dev.placements == legacy.placements
        np.testing.assert_allclose(dev.total_wastage_gbs,
                                   legacy.total_wastage_gbs, rtol=1e-6)

    def test_dag_replay(self):
        from repro.workloads import assert_release_order, scenarios
        wf = scenarios.get("workload_replay", n_tasks=300, seed=0)
        host = _host_sim().run(wf.to_jobs(under_frac=0.2, seed=0),
                               RetrySpec("ksplus"))
        dev = _dev_sim().run(wf.to_jobs(under_frac=0.2, seed=0),
                             RetrySpec("ksplus"))
        _assert_same(dev, host)
        assert_release_order(wf.to_jobs(seed=0), dev.placements)

    @pytest.mark.parametrize("seed", [0, 5])
    def test_node_churn(self, seed):
        faults = FaultSchedule.node_churn(_nodes(), rate=0.04,
                                          horizon=250.0, seed=seed)
        jobs = lambda: _timed_workload(48, seed=seed, under_frac=0.4)
        host = _host_sim().run(jobs(), RetrySpec("ksplus"), faults=faults)
        dev = _dev_sim().run(jobs(), RetrySpec("ksplus"), faults=faults)
        assert host.evictions > 0
        _assert_same(dev, host)
        assert dev.evictions == host.evictions
        assert dev.starvation_s == host.starvation_s

    def test_preemption_storm_join_mid_drain(self):
        """A storm kills most nodes at t=30 (mass eviction → long queue),
        then staggered rejoins land while that queue is still draining —
        every join triggers a fresh device drain over the backlog."""
        faults = FaultSchedule.preemption_storm(
            _nodes(), t=30.0, frac=0.9, seed=2, down_time=35.0)
        jobs = lambda: _timed_workload(56, seed=3, under_frac=0.5)
        host = _host_sim().run(jobs(), RetrySpec("ksplus"), faults=faults)
        dev = _dev_sim().run(jobs(), RetrySpec("ksplus"), faults=faults)
        assert host.evictions > 0
        _assert_same(dev, host)

    def test_parking_and_starvation(self):
        """Jobs bigger than every surviving node park (not spin) and
        unpark on rejoin; the device path must reproduce the host's
        starvation accounting exactly."""
        def jobs():
            out = _timed_workload(24, seed=4)
            # Fits only the 64 GB node, arrives while that node is down
            # -> parks until the t=120 rejoin.
            big = np.full(40, 56.0)
            out.append(Job(jid=900, family="t", input_gb=1.0, mem=big,
                           dt=1.0,
                           plan=AllocationPlan(np.zeros(1),
                                               np.asarray([60.0])),
                           est_runtime=40.0, release_time=30.0))
            return out
        faults = FaultSchedule([FaultEvent(20.0, "leave", 1),
                                FaultEvent(120.0, "join", 1, 96.0)])
        host = _host_sim().run(jobs(), RetrySpec("ksplus"), faults=faults)
        dev = _dev_sim().run(jobs(), RetrySpec("ksplus"), faults=faults)
        assert host.starvation_s > 0
        _assert_same(dev, host)
        assert dev.starvation_s == host.starvation_s

    def test_offset_sweep(self):
        cands = [OffsetCandidate(), OffsetCandidate(peak=0.25),
                 OffsetCandidate(peak=0.5)]
        host = _host_sim().run(_workload(32, seed=6), RetrySpec("ksplus"),
                               offsets=cands)
        dev = _dev_sim().run(_workload(32, seed=6), RetrySpec("ksplus"),
                             offsets=cands)
        for h, d in zip(host, dev):
            _assert_same(d, h)

    def test_drain_arg_validation(self):
        with pytest.raises(ValueError, match="drain"):
            ClusterSim(_nodes(), drain="gpu")
        with pytest.raises(ValueError, match="shard"):
            ClusterSim(_nodes(), drain="host", shard=2)


# ---------------------------------------------------------- elastic level
class TestElasticDeviceDrain:
    def test_fused_drain_matches_numpy(self):
        """Scripted submit/churn sequence: the fused planner (device
        drain) and the numpy planner must make identical placement and
        queueing decisions throughout."""
        logs = {}
        for backend in ("numpy", "fused"):
            rng = np.random.default_rng(21)
            pl = ElasticPlanner(backend=backend)
            pl.node_join("n0", 48.0)
            pl.node_join("n1", 32.0)
            alive = ["n0", "n1"]
            nxt, now, log = 2, 0.0, []
            for step in range(50):
                now += float(rng.uniform(0.0, 4.0))
                op = rng.uniform()
                if op < 0.5:
                    jid = f"j{step}"
                    log.append(("submit", jid, pl.submit(
                        jid, _storm_env(rng, float(rng.uniform(6, 30))),
                        now)))
                elif op < 0.7:
                    name = f"x{nxt}"
                    nxt += 1
                    alive.append(name)
                    placed = pl.node_join(name,
                                          float(rng.uniform(24, 64)),
                                          now=now)
                    log.append(("join", name, sorted(placed.items())))
                elif op < 0.9 and len(alive) > 1:
                    victim = alive.pop(int(rng.integers(0, len(alive))))
                    log.append(("leave", victim,
                                pl.node_leave(victim, now=now)))
                else:
                    log.append(("drain", None,
                                sorted(pl.drain(now).items())))
                log.append(("queued", None, pl.queued))
            logs[backend] = log
        assert logs["fused"] == logs["numpy"]

    def test_duplicate_jid_falls_back(self):
        """A queue holding the same jid twice takes the per-job admit
        loop (second occurrence is a resident live re-size) — both
        backends must agree on the outcome."""
        outs = {}
        for backend in ("numpy", "fused"):
            pl = ElasticPlanner(backend=backend)
            env = AllocationPlan(np.zeros(1), np.asarray([20.0]))
            pl.pending.append(("dup", env))
            pl.pending.append(("dup", env))
            pl.node_join("n0", 32.0)
            outs[backend] = (sorted(pl.drain(0.0).items()), pl.queued)
        assert outs["fused"] == outs["numpy"]
        assert outs["fused"][0] == [("dup", "n0")]


# ---------------------------------------------------------- sharded level
_SHARD_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
assert jax.device_count() >= 4, jax.device_count()
import sys
sys.path.insert(0, {tests_dir!r})
from test_admission_fused import _mk_lanes, _mk_state
from test_cluster_packed import _nodes, _workload
from repro.core import RetrySpec
from repro.sched import ClusterSim, Node
from repro.sched.admission import AdmissionState

# Unit: sharded drain == unsharded == numpy, both node-selection rules.
for select in ("first", "headroom"):
    out = {{}}
    for shard in (None, 2, 4):
        rng = np.random.default_rng(13)
        adm = AdmissionState((32.0, 48.0, 24.0, 40.0, 28.0, 36.0), K=3,
                             G=16, backend="fused", use_dur=True,
                             shard=shard)
        lanes = _mk_lanes(adm, rng, 18)
        out[shard] = adm.drain(2.0, lanes, select=select)
        assert adm.stats["drain_dispatches"] == 1, adm.stats
    rng = np.random.default_rng(13)
    ref = AdmissionState((32.0, 48.0, 24.0, 40.0, 28.0, 36.0), K=3,
                         G=16, backend="numpy", use_dur=True)
    lanes = _mk_lanes(ref, rng, 18)
    out["numpy"] = ref.drain(2.0, lanes, select=select)
    assert out[2] == out[None] == out["numpy"], (select, out)
    assert out[4] == out[None], (select, out)
    assert len(out[None]) > 0

# Engine: sharded ClusterSim replay matches the unsharded device drain.
plain = ClusterSim(_nodes() + [Node(3, 96.0)], engine="fused",
                   drain="device").run(_workload(48, seed=2),
                                       RetrySpec("ksplus"))
shard = ClusterSim(_nodes() + [Node(3, 96.0)], engine="fused",
                   drain="device", shard=2).run(_workload(48, seed=2),
                                                RetrySpec("ksplus"))
assert shard.placements == plain.placements
assert shard.retries == plain.retries
assert shard.makespan == plain.makespan
print("SHARDED-DRAIN-OK")
"""


class TestShardedDrain:
    def test_sharded_matches_unsharded(self):
        tests_dir = os.path.dirname(os.path.abspath(__file__))
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        src = os.path.join(os.path.dirname(tests_dir), "src")
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        out = subprocess.run(
            [sys.executable, "-c",
             _SHARD_CODE.format(tests_dir=tests_dir)],
            capture_output=True, text=True, env=env, timeout=540)
        assert out.returncode == 0, out.stderr[-4000:]
        assert "SHARDED-DRAIN-OK" in out.stdout
