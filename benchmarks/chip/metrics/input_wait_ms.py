"""Milliseconds per round in which chip 0 sat idle while the host assembled
a chunk's batch block: the part of the program's ``fl.assemble`` spans
(``fl/engine.run_fl_fused``'s ``assemble``: the draws and the device
placement) that no device op overlaps, in the window."""

from fedbench import spans


def read(ctx):
    s = spans.span_seconds(ctx, "fl.assemble", idle=True)
    return None if s is None else 1e3 * s / ctx.rounds
