"""Share of the traced window in which no operation ran on the device (%)."""

from chipbench import tracing


def read(ctx):
    return tracing.idle_pct(ctx["view"])
