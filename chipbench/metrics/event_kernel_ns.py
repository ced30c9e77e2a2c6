"""Device time of the Pallas event kernel per simulated request (ns)."""

from chipbench import tracing

# the event kernel: the only Mosaic custom call a closed-loop cell runs
KERNEL = r"pallas_grid.*tpu_custom_call"


def read(ctx):
    return tracing.device_ns(tracing.in_window(ctx["view"]), KERNEL,
                             ctx["work"])
