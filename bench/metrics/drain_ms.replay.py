"""Mean host wall time of one admission drain (``admission.drain`` span:
host preparation, the device program and its one readback), in ms."""

SPANS = ("admission.drain",)


def read(ctx):
    durs = [e["dur"] for e in ctx.get("spans") or []
            if e["name"] in SPANS]
    if not durs:
        return None
    return sum(durs) / len(durs) / 1e3
