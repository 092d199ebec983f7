#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python3 bench/readings.py --workload <name> --seeds 1,2,... \
        [--control-seeds 7,8,9] [--out readings.jsonl]

For each seed, in one process on the chip: the cell's set-up and a short
window (one whole pass of the timed path), then the numbers compared
against the plain reference (the lower readings).  For each control seed,
the control's numbers: the reference computed one precision below what
the configuration states, put in the program's place (the upper
readings).  The reference and the control run on the host, in threads
whose replays run in processes that import numpy only, while this
process drives the chip through the next seed.  Prints one JSON line
per seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import run  # noqa: E402


def _host_side(args):
    kind_name, c, outcomes, control = args
    kind = run.load_kind(kind_name)
    ref = kind.reference(c)
    out = {"lower": kind.readings(c, outcomes, ref)}
    if control:
        out["upper"] = kind.control_readings(c, ref)
    out["diagnostics"] = kind.diagnostics(c, ref)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="window of each seed (0: one pass)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctl = {int(s) for s in args.control_seeds.split(",") if s}

    bench = run.load_json("BENCHMARK.json")
    w, _, cfg, traffic = run.find_cell(bench, args.workload)
    kind = run.load_kind(traffic["kind"])
    run.use_cache_dir()
    run.devices_or_exit(int(w["chips"]))

    pool = ThreadPoolExecutor(max(1, min(len(seeds), os.cpu_count() // 3)))
    jobs = []
    for seed in sorted(set(seeds) | ctl):
        c = kind.build(cfg, traffic, seed)
        kind.warm(c, args.seconds)
        kind.window(c, args.seconds)
        outcomes = kind.program_outcomes(c)
        kind.release(c)
        jobs.append((seed, pool.submit(
            _host_side, (traffic["kind"], c, outcomes, seed in ctl))))
    sink = open(args.out, "w") if args.out else None
    for seed, fut in jobs:
        line = json.dumps({"workload": args.workload, "seed": seed,
                           **fut.result()})
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
    pool.shutdown()
    if sink:
        sink.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
