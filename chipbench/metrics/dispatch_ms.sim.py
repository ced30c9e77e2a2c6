"""Host time from the start of a ``simulate_network`` call to the start of
its engine's program on the device (the Pallas event kernel's dispatch or
the XLA event loop): per-p_hit ``compile_network``, tiling, transfers and
dispatch (ms)."""

from chipbench import tracing

PROGRAM = r"^jit_(pallas_grid|_simulate)"


def read(ctx):
    return tracing.dispatch_ms(ctx["view"], "sim.call", PROGRAM)
