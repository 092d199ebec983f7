#!/usr/bin/env python3
"""Bring-up smoke of the KS+ engine on a TPU, through its user entry points.

Run from the root of a checkout::

    python3 chip_smoke.py             # one chip: evaluation, replay, service
    python3 chip_smoke.py --chips 4   # four chips: the node-sharded drain only

One chip, three phases, each checked against its reference:

1. **evaluation** — ``evaluate_workflow(sarek(70), seed=0, train_frac=0.5)``
   over the default method zoo, whose replay runs ``simulate_fleet_many``
   on the compiled ``oom_probe`` Mosaic kernel.  The same jobs replayed
   with ``backend="jnp"`` must agree per lane: attempts and success
   exactly, wastage within ``rtol=1e-5``.
2. **replay** — the 8192-task ``workload_replay`` DAG through
   ``ClusterSim(engine="fused", drain="device")`` on 64 nodes, checked for
   DAG release order; a 600-task replay of the same scenario through the
   fused engine and the legacy per-job loop must place bitwise equal, with
   wastage within 1e-6 relative.
3. **service** — a few hundred ``predict`` and a few ``evaluate`` requests
   from 8 tenants through ``PredictionServer``/``ServeClient``; batched
   answers must be bitwise equal to unbatched ones.

``--chips 4`` runs only what exists across chips: ``ClusterSim(shard=4)``
(first-fit rule) and an ``ElasticPlanner(shard=4)`` under joins and leaves
(head-room rule), each against its unsharded twin, placements bitwise.

Each phase prints one JSON line (phase, wall and compile seconds, counts,
``passed``).  The last line is ``{"ok": true, "device": {...}}``.  There
is no CPU fallback: without a TPU, or outside a checkout, the script
exits non-zero and prints no result.  The persistent compile cache
follows :mod:`repro.compile_cache`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

from repro import compile_cache  # noqa: E402  (fails outside a checkout)

import numpy as np  # noqa: E402

SAREK_INSTANCES = 70      # benchmarks/run.py --full size of fig5's sarek
REPLAY_TASKS = 8192       # benchmarks/run.py --full workload_replay size
LEGACY_TASKS = 600        # benchmarks/run.py --full differential size
N_NODES = 64
NODE_CAPS_GB = (32.0, 48.0, 64.0, 96.0, 128.0)
TENANTS = 8
PREDICTS = 384
SHARD_TASKS = 2048
ELASTIC_JOBS = 128
ELASTIC_STEPS = 120

_compile = {"s": 0.0, "n": 0, "hits": 0}


def _listen_compiles():
    """Accumulate compile seconds (trace + lower + backend compile or
    persistent-cache load), backend compiles, and cache hits."""
    from jax import monitoring

    timed = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration",
             "/jax/core/compile/backend_compile_duration")

    def on_duration(event, duration, **kw):
        if event in timed:
            _compile["s"] += duration
        if event == timed[-1]:
            _compile["n"] += 1

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            _compile["hits"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


def _phase(name, fn, **kw):
    """Run one phase, print its JSON line, exit non-zero if it failed."""
    before = dict(_compile)
    t0 = time.perf_counter()
    counts, passed = fn(**kw)
    line = {"phase": name,
            "wall_s": time.perf_counter() - t0,
            "compile_s": _compile["s"] - before["s"],
            "compiles": _compile["n"] - before["n"],
            "cache_hits": _compile["hits"] - before["hits"],
            **counts, "passed": bool(passed)}
    print(json.dumps(line), flush=True)
    if not passed:
        raise SystemExit(f"chip_smoke: phase {name!r} failed")


def _nodes(seed=0):
    from repro.sched import Node
    caps = np.random.default_rng(seed).choice(NODE_CAPS_GB, N_NODES)
    return [Node(i, float(c)) for i, c in enumerate(caps)]


# ------------------------------------------------------------- one chip
def phase_evaluation(n=SAREK_INSTANCES, backend="auto"):
    import jax.numpy as jnp

    from repro.core import bucket_traces, fleet, registry
    from repro.sched import simulator
    from repro.traces import sarek

    wf = sarek(n)
    res = simulator.evaluate_workflow(wf, seed=0, train_frac=0.5)

    # The same jobs, per lane: the probe backend vs the jnp formulation.
    train, test = wf.split(0, 0.5, 1.0)
    names = registry.method_names()
    fitted = simulator._fit_methods(wf, train, names, 4, 128.0)
    jobs = simulator._method_jobs(fitted, train, test, names)
    traces = bucket_traces([e.mem for f in train for e in test[f]])
    dev = fleet.simulate_fleet_many(jobs, traces, 1.0, machine_memory=128.0,
                                    backend=backend)
    ref = fleet.simulate_fleet_many(jobs, traces, 1.0, machine_memory=128.0,
                                    backend="jnp")
    attempts = all(np.array_equal(d.attempts, r.attempts)
                   for d, r in zip(dev, ref))
    succeeded = all(np.array_equal(d.succeeded, r.succeeded)
                    for d, r in zip(dev, ref))
    rel = max(float(np.max(np.abs(d.wastage_gbs - r.wastage_gbs)
                           / np.maximum(np.abs(r.wastage_gbs), 1e-30)))
              for d, r in zip(dev, ref))
    wastage = all(np.allclose(d.wastage_gbs, r.wastage_gbs, rtol=1e-5,
                              atol=0.0) for d, r in zip(dev, ref))
    # evaluate_workflow replayed exactly these lanes on the "auto" backend.
    resolved = fleet.resolve_backend(backend)
    auto = dev if fleet.resolve_backend("auto") == resolved else ref
    totals = all(res.methods[m].total_gbs == float(a.wastage_gbs.sum())
                 for m, a in zip(names, auto))

    # Evidence the probe is a Mosaic kernel: compile attempt #1 of the
    # first method over the first bucket as simulate_fleet_many does.
    b = traces.buckets[0]
    starts, peaks, _ = jobs[0][0]
    bs = np.full((b.dmems.shape[0], starts.shape[1]), fleet.PAD_START,
                 np.float32)
    bp = np.ones_like(bs)
    bs[:len(b.idx)], bp[:len(b.idx)] = starts[b.idx], peaks[b.idx]
    group = ((bs, bp, b.dmems, b.dmemsneg, b.dlengths, b.dsummem),)
    hlo = fleet._probe_many.lower(group, jnp.float32(128.0), dt=1.0,
                                  backend=resolved).compile().as_text()
    mosaic = "tpu_custom_call" in hlo
    counts = {"lanes": traces.n, "methods": len(names),
              "buckets": len(traces.buckets),
              "probe_backend": resolved, "tpu_custom_call": mosaic,
              "max_rel_wastage_err": rel,
              "attempts_equal": attempts, "succeeded_equal": succeeded,
              "evaluate_totals_equal": totals,
              "ks+_total_gbs": res.methods["ks+"].total_gbs}
    passed = attempts and succeeded and wastage and totals and (
        mosaic or resolved != "pallas")
    return counts, passed


def phase_replay(n_big=REPLAY_TASKS, n_small=LEGACY_TASKS):
    from repro.core import RetrySpec, ksplus_retry
    from repro.sched import ClusterSim
    from repro.workloads import assert_release_order, scenarios

    big = scenarios.get("workload_replay", n_tasks=n_big, seed=1)
    jobs = big.to_jobs(under_frac=0.1, seed=1)
    t0 = time.perf_counter()
    bres = ClusterSim(_nodes(), engine="fused", drain="device").run(
        jobs, RetrySpec("ksplus"))
    big_s = time.perf_counter() - t0
    assert_release_order(jobs, bres.placements)

    small = scenarios.get("workload_replay", n_tasks=n_small, seed=1)
    fres = ClusterSim(_nodes(), engine="fused", drain="device").run(
        small.to_jobs(under_frac=0.2, seed=1), RetrySpec("ksplus"))
    lres = ClusterSim(_nodes(), engine="legacy").run(
        small.to_jobs(under_frac=0.2, seed=1), ksplus_retry)
    rel = abs(fres.total_wastage_gbs - lres.total_wastage_gbs) / max(
        abs(lres.total_wastage_gbs), 1e-30)
    same = (fres.placements == lres.placements
            and fres.retries == lres.retries
            and fres.unschedulable == lres.unschedulable)
    counts = {"tasks": n_big, "nodes": N_NODES, "replay_s": big_s,
              "placements": len(bres.placements), "retries": bres.retries,
              "unschedulable": bres.unschedulable,
              "makespan_s": bres.makespan,
              "legacy_tasks": n_small,
              "legacy_placements": len(lres.placements),
              "placements_bitwise": bool(same), "wastage_rel_err": rel}
    passed = same and rel <= 1e-6 and bres.unschedulable == 0
    return counts, passed


def phase_service(tenants=TENANTS, n_requests=PREDICTS):
    from repro.serve.bench import FAMILIES, build_server, request_tape

    tape = request_tape(n_requests, tenants, seed=0)
    plans, evals = {}, {}
    for batching in (True, False):
        srv = build_server(tenants=tenants, batching=batching,
                           cache_predictions=False, seed=0)
        futs = [srv.client(t).predict_async(f, x) for t, f, x in tape]
        evs = [srv.client(f"tenant{t}").evaluate_async(
            FAMILIES[t % len(FAMILIES)][0]) for t in range(tenants)]
        srv.drain()
        plans[batching] = [fu.result(0) for fu in futs]
        evals[batching] = [
            (e.total_gbs, e.n, e.succeeded, e.mean_attempts)
            for e in (fu.result(0) for fu in evs)]
        if batching:
            batches = srv.stats()["batcher"]["batches"]
    bitwise = all(np.array_equal(p.starts, q.starts)
                  and np.array_equal(p.peaks, q.peaks)
                  for p, q in zip(plans[True], plans[False]))
    evals_equal = evals[True] == evals[False]
    counts = {"tenants": tenants, "predicts": n_requests,
              "evaluates": tenants, "batches": batches,
              "plans_bitwise": bool(bitwise), "evaluates_equal": evals_equal}
    return counts, bitwise and evals_equal


# ----------------------------------------------------------- four chips
def phase_sharded_replay(shard=4, n_tasks=SHARD_TASKS):
    from repro.core import RetrySpec
    from repro.sched import ClusterSim
    from repro.workloads import scenarios

    wf = scenarios.get("workload_replay", n_tasks=n_tasks, seed=2)
    out = {}
    for s in (None, shard):
        t0 = time.perf_counter()
        res = ClusterSim(_nodes(), engine="fused", drain="device",
                         shard=s).run(wf.to_jobs(under_frac=0.1, seed=2),
                                      RetrySpec("ksplus"))
        out[s] = (res, time.perf_counter() - t0)
    (plain, plain_s), (sh, sh_s) = out[None], out[shard]
    same = (sh.placements == plain.placements and sh.retries == plain.retries
            and sh.makespan == plain.makespan)
    counts = {"select": "first", "shard": shard, "tasks": n_tasks,
              "nodes": N_NODES, "placements": len(sh.placements),
              "unsharded_s": plain_s, "sharded_s": sh_s,
              "placements_bitwise": bool(same)}
    return counts, same


def _elastic_plan(rng):
    from repro.core import AllocationPlan

    k = int(rng.integers(1, 5))
    starts = np.concatenate([[0.0], np.sort(rng.uniform(5.0, 300.0, k - 1))])
    return AllocationPlan(starts, np.sort(rng.uniform(16.0, 96.0, k)))


def _elastic_log(shard, jobs=None, steps=None, seed=0):
    """A seeded script on ``N_NODES`` slices: ``jobs`` submissions that
    overfill the pool, then ``steps`` of leaves, joins, drains and
    finish-then-resubmit (the finished job's lane is recycled, so the
    lane state keeps its shape).  Returns the decision log and how many
    devices the drain's state spans."""
    from repro.sched import ElasticPlanner

    jobs = ELASTIC_JOBS if jobs is None else jobs
    steps = ELASTIC_STEPS if steps is None else steps
    rng = np.random.default_rng(seed)
    pl = ElasticPlanner(backend="fused", shard=shard)
    alive = []
    for i, cap in enumerate(rng.choice(NODE_CAPS_GB, N_NODES)):
        pl.node_join(f"n{i}", float(cap))
        alive.append(f"n{i}")
    log, now = [], 0.0
    for j in range(jobs):
        now += float(rng.uniform(0.0, 1.0))
        log.append(("submit", f"j{j}",
                    pl.submit(f"j{j}", _elastic_plan(rng), now)))
    next_node, next_job = N_NODES, jobs
    for step in range(steps):
        now += float(rng.uniform(0.0, 4.0))
        op = rng.uniform()
        if op < 0.3 and len(alive) > 1:
            victim = alive.pop(int(rng.integers(0, len(alive))))
            log.append(("leave", victim, pl.node_leave(victim, now=now)))
        elif op < 0.6:
            name = f"n{next_node}"
            next_node += 1
            alive.append(name)
            log.append(("join", name, sorted(pl.node_join(
                name, float(rng.choice(NODE_CAPS_GB)), now=now).items())))
        elif op < 0.9:
            resident = sorted(j for sl in pl.slices.values()
                              for j, _, _ in sl.jobs)
            if resident:
                done = resident[int(rng.integers(0, len(resident)))]
                pl.finish(done)
                log.append(("finish", done, sorted(pl.drain(now).items())))
                jid = f"j{next_job}"
                next_job += 1
                log.append(("submit", jid,
                            pl.submit(jid, _elastic_plan(rng), now)))
        else:
            log.append(("drain", step, sorted(pl.drain(now).items())))
        log.append(("queued", step, pl.queued))
    dadmit = getattr(pl._adm, "_dadmit", None)
    span = len(dadmit.sharding.device_set) if dadmit is not None else 0
    return log, span


def phase_sharded_elastic(shard=4, jobs=None, steps=None):
    plain, _ = _elastic_log(None, jobs, steps)
    sharded, span = _elastic_log(shard, jobs, steps)
    drained = sum(len(got) for op, _, got in sharded
                  if op in ("join", "leave", "finish", "drain"))
    same = sharded == plain
    counts = {"select": "headroom", "shard": shard,
              "jobs": ELASTIC_JOBS if jobs is None else jobs,
              "steps": ELASTIC_STEPS if steps is None else steps,
              "nodes_initial": N_NODES, "log_entries": len(sharded),
              "drain_decisions": drained, "mesh_devices": span,
              "decisions_bitwise": bool(same)}
    return counts, same and span == shard


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    compile_cache.setup()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX reports "
                         f"{devices[0].platform!r}); nothing was run")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} TPU devices, found {len(devices)}")
    _listen_compiles()
    if args.chips == 4:
        _phase("sharded_replay", phase_sharded_replay)
        _phase("sharded_elastic", phase_sharded_elastic)
    else:
        _phase("evaluation", phase_evaluation)
        _phase("replay", phase_replay)
        _phase("service", phase_service)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
