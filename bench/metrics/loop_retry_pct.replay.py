"""Share of the replay's host event loop spent re-planning OOM-killed
tasks: ``cluster.retry`` span time (the compacted ``retry_packed``
re-plan, the ``need``/``bounds`` refresh and the float64 re-probe) over
``cluster.run`` span time, in %.  Read from the spans alone, on any
backend."""

SPANS = ("cluster.run", "cluster.retry")


def read(ctx):
    loop, replan = SPANS
    spans = ctx.get("spans") or []
    run = sum(e["dur"] for e in spans if e["name"] == loop)
    retry = [e["dur"] for e in spans if e["name"] == replan]
    if run <= 0 or not retry:
        return None
    return 100.0 * sum(retry) / run
