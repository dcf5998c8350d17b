"""Host milliseconds per round inside the program's ``fl.assemble`` spans
(``fl/engine.run_fl_fused``'s ``assemble``: the chunk's batch draws and
their device placement), in the window, the device busy or not."""

from fedbench import spans


def read(ctx):
    s = spans.span_seconds(ctx, "fl.assemble")
    return None if s is None else 1e3 * s / ctx.rounds
