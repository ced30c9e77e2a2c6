"""Device time of the replay kernel per lane-request (ns)."""

from chipbench import tracing

# the replay kernel: the only Mosaic custom call a replay cell runs
KERNEL = r"pallas_grid.*tpu_custom_call"


def read(ctx):
    return tracing.device_ns(tracing.in_window(ctx["view"]), KERNEL,
                             ctx["work"])
