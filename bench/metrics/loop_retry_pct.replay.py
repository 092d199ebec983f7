"""Share of the replay's host event loop spent re-planning OOM-killed
tasks: ``cluster.retry`` span time (the compacted ``retry_packed``
re-plan, the ``need``/``bounds`` refresh and the float64 re-probe) over
``cluster.run`` span time, in %.  Read on chip runs only: a traced run
whose profiler trace has no device plane reads nothing (PERF.md,
section 3)."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not tr.devices:
        return None
    spans = ctx.get("spans") or []
    run = sum(e["dur"] for e in spans if e["name"] == "cluster.run")
    retry = [e["dur"] for e in spans if e["name"] == "cluster.retry"]
    if run <= 0 or not retry:
        return None
    return 100.0 * sum(retry) / run
