"""Host time of the decode of one policy's replay to the user's answer:
``unpack_grid_ops``, the per-lane classes and ``empirical_network`` (ms)."""

from chipbench import tracing


def read(ctx):
    return tracing.span_ms(ctx["view"], "replay.decode")
