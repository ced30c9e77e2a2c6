"""Host time from the start of a ``replay_grid_pallas`` call to the start
of its kernel's program on the device: lane set-up, transfers and dispatch
(ms)."""

from chipbench import tracing

# the jitted kernel dispatch of kernels/replay.py
PROGRAM = r"^jit_pallas_grid\("


def read(ctx):
    return tracing.dispatch_ms(ctx["view"], "replay.dispatch", PROGRAM)
