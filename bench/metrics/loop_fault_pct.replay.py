"""Share of the replay's host event loop spent on node membership
events: ``cluster.leave`` (evicting a reclaimed node's residents,
charging their wastage, requeueing or dooming them) plus
``cluster.join`` (a node's row back, parked tasks unparked) span time,
over ``cluster.run`` span time, in %.  The drains that follow each event
are not in it.  Read from the spans alone, on any backend; nothing where
the replay recorded no leave or no join."""

SPANS = ("cluster.run", "cluster.leave", "cluster.join")


def read(ctx):
    loop, leave, join = SPANS
    spans = ctx.get("spans") or []
    run = sum(e["dur"] for e in spans if e["name"] == loop)
    left = [e["dur"] for e in spans if e["name"] == leave]
    joined = [e["dur"] for e in spans if e["name"] == join]
    if run <= 0 or not left or not joined:
        return None
    return 100.0 * (sum(left) + sum(joined)) / run
