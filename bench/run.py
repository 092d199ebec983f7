#!/usr/bin/env python3
"""The benchmark's one command.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell asks
for.  Everything is found by name in ``BENCHMARK.json``: the cell gives a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the mix's ``kind`` names the code
that sets it up, runs its window and checks it, with the faults its
tests plant (``bench/kinds/<kind>.py``); each per-layer metric is read
by ``bench/metrics/<metric>.py``, whose ``SPANS``, where the metric is
read from the program's spans, names the spans it needs.  So a cell of
a new kind, with metrics of its own, comes in as new files and entries.

A run sets up (data from the seed, fits, one warm-up pass over every
shape the window uses), measures for ``--seconds`` with nothing compiling,
then checks what the window produced against the plain reference in
``bench/reference``.  ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` runs the window under the profiler and the program's span
tracer and reports its per-layer metrics, device busy time and a
breakdown.  Earlier lines of standard output carry diagnostics; the last
is one JSON object.  The numbers compared, each with its limit, are the
last lines of standard error and the last key of that object.

Without a TPU, or with fewer chips than the cell asks for, the command
exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def find_cell(bench: dict, workload: str):
    """The cell, its configuration entry, configuration and traffic."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    traffic = load_json("bench", "traffic", w["traffic"] + ".json")
    return w, entry, cfg, traffic


def metrics_of(bench: dict, workload: str, kind_e2e: list):
    """The cell's end-to-end and per-layer metric entries."""
    def listed(m, fallback):
        return workload in m["workloads"] if "workloads" in m else fallback
    e2e = [m for m in bench["end_to_end"]
           if listed(m, m["name"] == "setup_s" or m["name"] in kind_e2e)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if listed(m, m["moves"] in names)]
    return e2e, layer


def reader(name: str):
    """``bench/metrics/<name>.py``'s ``read(ctx)``."""
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_kind(name: str):
    """``bench/kinds/<name>.py``, from this checkout, as the module
    ``bench.kinds.<name>``: the name by which the processes it spawns
    unpickle its functions and data.  Loaded once a process; a module of
    that name from another file is refused, not replaced."""
    path = os.path.join(ROOT, "bench", "kinds", name + ".py")
    module = "bench.kinds." + name
    mod = sys.modules.get(module)
    if mod is not None:
        if os.path.realpath(mod.__file__) != os.path.realpath(path):
            raise RuntimeError(f"bench: {module} is already loaded from "
                               f"{mod.__file__}, not {path}")
        return mod
    spec = importlib.util.spec_from_file_location(module, path)
    mod = sys.modules[module] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Compiles:
    """Backend compiles, persistent-cache hits and compile seconds
    (trace, lowering, compile or cache load), from ``jax.monitoring``."""

    TIMED = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration",
             "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        from jax import monitoring
        self.n = self.hits = 0
        self.s = 0.0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event in self.TIMED:
            self.s += duration
        if event == self.TIMED[-1]:
            self.n += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def use_cache_dir() -> None:
    """JAX's persistent compilation cache at the checkout's fixed path,
    every program cached; the program takes the directory from here."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    from repro import compile_cache
    compile_cache.setup()
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)


def devices_or_exit(chips: int, require_tpu: bool = True):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX reports "
                         f"{devs[0].platform!r}); nothing was measured")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell asks for {chips} chips, JAX "
                         f"finds {len(devs)}")
    return devs[:chips]


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # the Python tracer would slow the host
    opts.host_tracer_level = 1     # annotations only
    return opts


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bench: dict = None, require_tpu: bool = True,
             cell: tuple = None, setup_cache: bool = True,
             out=print) -> dict:
    """One run of one cell; returns the result object it prints last."""
    bench = bench if bench is not None else load_json("BENCHMARK.json")
    w, entry, cfg, traffic = (cell if cell is not None
                              else find_cell(bench, workload))
    kind = load_kind(traffic["kind"])
    e2e, layer = metrics_of(bench, workload, [kind.END_TO_END])

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise SystemExit(f"bench: no program under {ROOT}/src; nothing "
                         f"was measured")
    if setup_cache:
        use_cache_dir()
    devs = devices_or_exit(int(w["chips"]), require_tpu)
    import jax

    comp = Compiles()
    c = kind.build(cfg, traffic, seed)
    warm = kind.warm(c, seconds)
    setup_s = time.perf_counter() - T_START
    n0 = comp.n

    spans, tr = None, None
    if trace:
        from repro.obs import trace as obs
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR,
                                 profiler_options=_profile_options())
        obs.enable(ring=4_000_000)
        obs.clear()
        try:
            win = kind.window(c, seconds, around=jax.profiler.TraceAnnotation)
        finally:
            obs.disable()
            jax.profiler.stop_trace()
        spans = obs.events()
        obs.clear()
        from bench import profile
        path = profile.find(TRACE_DIR)
        tr = profile.load(path) if path else None
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        win = kind.window(c, seconds)
    compiles_in_window = comp.n - n0
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak(devs)}

    outcomes = kind.program_outcomes(c)
    kind.release(c)
    t_ref = time.perf_counter()
    ref = kind.reference(c)
    checks = kind.readings(c, outcomes, ref)
    ref_s = time.perf_counter() - t_ref
    limits = kind.LIMITS
    correct = compiles_in_window == 0 and all(
        math.isfinite(v) and v <= limits[k] for k, v in checks.items())

    out(json.dumps({"diagnostics": {
        "workload": workload, "seed": seed, "setup_s": setup_s,
        "compiles_in_window": compiles_in_window,
        "compiles_setup": n0, "cache_hits": comp.hits,
        "compile_s": comp.s, "window_s": c.wall_s, "reference_s": ref_s,
        **warm, **win["counts"], **kind.diagnostics(c, ref)}}))

    units = {m["name"]: m["unit"] for m in e2e + layer}
    if trace:
        ctx = {"spans": spans, "trace": tr, "window_s": c.wall_s,
               "counts": win["counts"], "device_kind": devs[0].device_kind}
        metrics = {}
        for m in layer:
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s() if tr else None
        device["window_s"] = c.wall_s
    else:
        vals = dict(win["metrics"], setup_s=setup_s)
        metrics = {m["name"]: {"value": vals[m["name"]],
                               "unit": units[m["name"]]} for m in e2e}
    result = {"correct": bool(correct), "attempted": win["attempted"],
              "failed": win["failed"], "metrics": metrics, "device": device}
    if trace and tr is not None:
        result["breakdown"] = {"device_ops": tr.op_totals(10),
                               "idle_gaps": tr.idle_gaps(10)}
    result["checks"] = {
        "compiles_in_window": {"value": compiles_in_window, "limit": 0},
        **{k: {"value": v, "limit": limits[k]} for k, v in checks.items()}}
    for k, v in result["checks"].items():
        print(f"check {k} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    out(json.dumps(result))
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
             out=lambda s: print(s, flush=True))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
