"""CLI: ``python -m repro.obs summarize <trace>`` or ``python -m
repro.obs summarize --xplane <file>``.

The first reads a trace exported by :mod:`repro.obs.export` (Chrome-trace
JSON or JSONL) and prints the per-tag time/dispatch/compile breakdown.
The second reads a JAX profiler trace (``*.xplane.pb``, or gzipped) and
prints, per program span, its count, total and self time and the device
idle time charged to it (:mod:`repro.obs.xplane`).
"""

from __future__ import annotations

import argparse
import sys

from repro.obs.export import read_events, summarize


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="python -m repro.obs")
    sub = parser.add_subparsers(dest="cmd", required=True)
    p_sum = sub.add_parser(
        "summarize",
        help="per-tag time/dispatch/compile breakdown of a trace file, "
             "or device idle time by host span of a profiler trace")
    p_sum.add_argument("trace", nargs="?",
                       help="Chrome-trace JSON or JSONL event log")
    p_sum.add_argument("--xplane", metavar="FILE",
                       help="JAX profiler trace (*.xplane.pb[.gz])")
    args = parser.parse_args(argv)
    if args.cmd == "summarize":
        if (args.trace is None) == (args.xplane is None):
            parser.error("summarize takes a trace file or --xplane FILE")
        if args.xplane is not None:
            from repro.obs.xplane import summarize_xplane
            print(summarize_xplane(args.xplane))
        else:
            print(summarize(read_events(args.trace)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
