"""Mean device time of the admission drain per drain, in ms: device time
of the drain programs in the profiler trace (the jitted drain and its
candidate pre-filter, modules named ``jit_kernel``) over the number of
``admission.drain`` spans.  float64 has no published peak, so there is
no roofline share."""


def _is_drain(name):
    return name.split("(")[0].strip() == "jit_kernel"


def read(ctx):
    tr = ctx.get("trace")
    drains = sum(1 for e in ctx.get("spans") or []
                 if e["name"] == "admission.drain")
    if tr is None or drains == 0:
        return None
    s, runs = tr.module_s(_is_drain)
    if runs == 0:
        return None
    return 1e3 * s / drains
