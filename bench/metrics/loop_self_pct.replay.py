"""Share of the replay's host event loop not spent in admission drains:
``cluster.run`` span time minus the ``admission.drain`` spans inside it,
over the ``cluster.run`` span time, in %."""

# The spans it needs: the drains are subtracted where there are any.
SPANS = ("cluster.run",)
DRAIN = "admission.drain"


def read(ctx):
    spans = ctx.get("spans") or []
    run = sum(e["dur"] for e in spans if e["name"] in SPANS)
    drain = sum(e["dur"] for e in spans if e["name"] == DRAIN)
    if run <= 0:
        return None
    return 100.0 * (run - drain) / run
