"""Mean host time of ``admission.drain.readback`` per ``admission.drain``
span, in ms: leaving the x64 scope and the one batched ``device_get``,
the host blocked on the drain program and the transfer back.  Read from
the spans alone, on any backend."""

SPANS = ("admission.drain", "admission.drain.readback")


def read(ctx):
    drain, readback = SPANS
    spans = ctx.get("spans") or []
    drains = sum(1 for e in spans if e["name"] == drain)
    durs = [e["dur"] for e in spans if e["name"] == readback]
    if drains == 0 or not durs:
        return None
    return sum(durs) / drains / 1e3
