"""``python -m repro.serve`` — run the saturation harness and print JSON.

A quick operator smoke test of the serving stack: seeded multi-tenant
traffic through batched and unbatched servers, the virtual-clock latency
loop, and the cache/compile discipline checks (see
:mod:`repro.serve.bench`).
"""

from __future__ import annotations

import argparse
import json

from repro import compile_cache


def main(argv=None) -> int:
    compile_cache.setup()
    from repro.serve.bench import run_saturation

    ap = argparse.ArgumentParser(
        "python -m repro.serve",
        description="serve_saturation: multi-tenant micro-batched "
                    "prediction service benchmark")
    ap.add_argument("--tenants", type=int, default=8)
    ap.add_argument("--requests", type=int, default=2048,
                    help="throughput-phase request count")
    ap.add_argument("--rate", type=float, default=2000.0,
                    help="latency-phase open-loop arrival rate (req/s)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    out = run_saturation(tenants=args.tenants, n_requests=args.requests,
                         rate_rps=args.rate, seed=args.seed)
    print(json.dumps(out, indent=2, default=str))
    thr = out["throughput"]
    ok = bool(thr["bitwise"]) and bool(
        out["discipline"]["warm_zero_compiles"])
    print(f"# speedup {thr['speedup_x']:.1f}x, "
          f"p99 {out['latency']['p99_ms']:.3f} ms, "
          f"bitwise={thr['bitwise']}, "
          f"warm_zero_compiles={out['discipline']['warm_zero_compiles']}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
