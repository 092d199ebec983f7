"""Mean host time of ``admission.drain.launch`` per ``admission.drain``
span, in ms: the call of the drain program, its asynchronous enqueue on
the device.  Read from the spans alone, on any backend."""

SPANS = ("admission.drain", "admission.drain.launch")


def read(ctx):
    drain, launch = SPANS
    spans = ctx.get("spans") or []
    drains = sum(1 for e in spans if e["name"] == drain)
    durs = [e["dur"] for e in spans if e["name"] == launch]
    if drains == 0 or not durs:
        return None
    return sum(durs) / drains / 1e3
