"""Device milliseconds per round of aggregation and the server step: the
ops the round body names ``fl_aggregate`` or ``fl_server``
(``jax.named_scope`` in ``fl/engine``), the union of their intervals on
each chip, summed over the chips."""

from fedbench.layers import union_seconds

SCOPES = ("/fl_aggregate/", "/fl_server/")


def read(ctx):
    ns = sum(union_seconds([(o.start, o.end) for o in ctx.ops
                            if o.chip == c
                            and any(m in o.path for m in SCOPES)])[0]
             for c in range(ctx.chips))
    return 1e3 * ns / 1e9 / ctx.rounds if ns > 0 else None
