"""Mean host time of ``admission.drain.operands`` per ``admission.drain``
span, in ms: the numpy build of the drain program's operands (run index,
capacities, node mask), queue padding, entering the x64 scope, the
device-state sync where it is stale, and the one upload of the packed
float64 operand vector (the node-sharded drain: six uploads and two
scalar converts).  Read from the spans alone, on any backend."""

SPANS = ("admission.drain", "admission.drain.operands")


def read(ctx):
    drain, operands = SPANS
    spans = ctx.get("spans") or []
    drains = sum(1 for e in spans if e["name"] == drain)
    durs = [e["dur"] for e in spans if e["name"] == operands]
    if drains == 0 or not durs:
        return None
    return sum(durs) / drains / 1e3
