"""Device milliseconds per round of local training: the ops inside the
vmapped ``local_train`` (``fl/simulation.make_local_train``, a ``jax.jit``
that names them in its ``op_name``), summed over the chips."""


def read(ctx):
    s = ctx.op_seconds("jit(local_train)")
    return 1e3 * s / ctx.rounds if s > 0 else None
