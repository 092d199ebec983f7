"""Mean number of same-instant events one admission drain folds (the
``steps`` arg of the ``admission.drain`` span): 1.0 where every event
drains on its own.  A span without ``steps`` is one per-event drain."""

SPANS = ("admission.drain",)


def read(ctx):
    steps = [(e.get("args") or {}).get("steps", 1)
             for e in ctx.get("spans") or [] if e["name"] in SPANS]
    if not steps:
        return None
    return sum(steps) / len(steps)
