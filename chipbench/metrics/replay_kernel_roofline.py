"""The replay kernel's share of its roofline (%).

The replay's semantics fix its traffic, whatever implements it: per
lane-request 12 bytes in (a 4-byte key, coin and fetch window) and 10 bytes
out (a 1-byte hit, the 4-byte evicted key, the 4-byte op word and a 1-byte
class). It does no floating-point work worth counting, so the bound is the
bytes over the chip's HBM bandwidth, divided by the kernel's device time.
"""

from chipbench import tracing

# the replay kernel: the only Mosaic custom call a replay cell runs
KERNEL = r"pallas_grid.*tpu_custom_call"
BYTES_PER_REQUEST = 12 + 10


def read(ctx):
    ns = tracing.device_ns(tracing.in_window(ctx["view"]), KERNEL,
                           ctx["work"])
    if ns is None:
        return None
    least_ns = BYTES_PER_REQUEST / ctx["peaks"]()["hbm_bytes_per_s"] * 1e9
    return 100.0 * least_ns / ns
