"""Mean host time of ``admission.drain.launch`` per ``admission.drain``
span, in ms: the call of the drain program, its asynchronous enqueue on
the device.  Read on chip runs only: a traced run whose profiler trace
has no device plane reads nothing (PERF.md, section 3)."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not tr.devices:
        return None
    spans = ctx.get("spans") or []
    drains = sum(1 for e in spans if e["name"] == "admission.drain")
    durs = [e["dur"] for e in spans
            if e["name"] == "admission.drain.launch"]
    if drains == 0 or not durs:
        return None
    return sum(durs) / drains / 1e3
