"""Seconds of jaxpr tracing, lowering and backend compilation (or loading
from the persistent cache) before the window, from ``jax.monitoring``'s
compile events: both ``run_fl`` calls' chunk programs and evals."""


def read(ctx):
    return ctx.pipeline_s
