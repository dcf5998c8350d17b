"""Device milliseconds per round of the routed experts (``models/moe.py``:
router, dispatch and combine one-hots, the held experts' GEMMs): the ops
whose ``op_name`` holds the scope ``moe_routed`` (a ``jax.named_scope``;
forward, rematerialised and backward ops carry it, the latter wrapped as
``jvp(moe_routed)`` or ``transpose(jvp(moe_routed))``), the union of their
intervals on each chip, summed over the chips. None where no op carries it."""

import re

from fedbench.layers import union_seconds

SCOPE = re.compile(r"(^|[/(])moe_routed([/)]|$)")


def read(ctx):
    ns = sum(union_seconds([(o.start, o.end) for o in ctx.ops
                            if o.chip == c and SCOPE.search(o.path)])[0]
             for c in range(ctx.chips))
    return 1e3 * ns / 1e9 / ctx.rounds if ns > 0 else None
