"""Share of the traced replay window in which no operation ran on the
device, in %."""

from bench.profile import idle_pct as read  # noqa: F401
