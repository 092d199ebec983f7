"""Share of the replay's host event loop not spent in admission drains:
``cluster.run`` span time minus the ``admission.drain`` spans inside it,
over the ``cluster.run`` span time, in %."""


def read(ctx):
    spans = ctx.get("spans") or []
    run = sum(e["dur"] for e in spans if e["name"] == "cluster.run")
    drain = sum(e["dur"] for e in spans if e["name"] == "admission.drain")
    if run <= 0:
        return None
    return 100.0 * (run - drain) / run
