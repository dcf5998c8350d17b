"""Federated training driver.

Two modes:

  * ``--mode sim`` (default): the benchmark-scale FL loop (repro.fl) -- real
    learning on the synthetic LM task with exact uplink accounting; runs on
    whatever devices exist (CPU in this container).

  * ``--mode spmd``: the production SPMD round step (the same function the
    dry-run lowers) executed on a local mesh with a reduced architecture --
    end-to-end proof that the distributed round actually steps, not only
    compiles.

Examples:
  PYTHONPATH=src python -m repro.launch.train --mode sim --method gradestc --rounds 30
  PYTHONPATH=src python -m repro.launch.train --mode spmd --arch gemma3-1b --rounds 3
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import time


def _run_sim(args) -> int:
    from repro.fl import FLConfig, run_fl

    cfg = FLConfig(
        method=args.method,
        rounds=args.rounds,
        n_clients=args.clients,
        local_steps=args.local_steps,
        alpha=args.alpha,
        lr=args.lr,
        seed=args.seed,
        eval_every=max(1, args.rounds // 10),
    )

    def progress(rnd, info):
        print(f"round {rnd:4d} loss={info['loss']:.4f} acc={info['acc']:.4f} "
              f"uplink={info['uplink']/2**20:.2f}MiB", flush=True)

    res = run_fl(cfg, progress=progress)
    print("---")
    print(res.ledger.summary())
    print(f"final loss {res.eval_loss[-1]:.4f}  acc {res.eval_acc[-1]:.4f}  "
          f"wall {res.wall_s:.1f}s")
    return 0


def _run_spmd(args) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.data import client_batch_stream, make_task
    from repro.launch.mesh import make_local_mesh
    from repro.launch.sharding import make_plan, param_specs
    from repro.launch.steps import (
        compression_policy_for, make_fl_round_step, make_ge_state,
        ge_state_specs,
    )
    from repro.models import model

    cfg = get_config(args.arch).reduced()
    cfg = dataclasses.replace(cfg, vocab=256)
    n_dev = len(jax.devices())
    mesh = make_local_mesh((n_dev, 1), ("data", "model"))
    plan = make_plan(mesh, cfg)
    policy = compression_policy_for(cfg, plan)
    C = plan.n_clients

    step = make_fl_round_step(cfg, mesh, plan, policy, method=args.method,
                              lr=args.lr, local_steps=args.local_steps)
    params = model.init_params(cfg, jax.random.PRNGKey(args.seed))
    ge_state = make_ge_state(cfg, policy, C, seed=args.seed)
    step_j = jax.jit(step)

    task = make_task(vocab=cfg.vocab, n_clients=C, alpha=args.alpha, seed=args.seed)
    streams = [client_batch_stream(task, c, args.batch, args.seq, args.seed)
               for c in range(C)]
    evalb = next(client_batch_stream(task, -1, args.batch, args.seq, 77))

    @jax.jit
    def eval_loss(p, b):
        from repro.models import loss_fn
        return loss_fn(cfg, p, b)

    for rnd in range(args.rounds):
        t0 = time.time()
        bs = [next(s) for s in streams]
        batches = {k: jnp.stack([b[k] for b in bs]) for k in bs[0]}
        params, ge_state, metrics = step_j(params, ge_state, batches)
        l = float(eval_loss(params, evalb))
        print(f"round {rnd}: eval_loss={l:.4f}  ({time.time()-t0:.1f}s)", flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", choices=["sim", "spmd"], default="sim")
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--method", default="gradestc")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--clients", type=int, default=10)
    ap.add_argument("--local-steps", dest="local_steps", type=int, default=2)
    ap.add_argument("--alpha", type=float, default=None,
                    help="Dirichlet non-IID (0.5/0.1); default IID")
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    # Host tuning first: XLA_FLAGS and logging knobs are frozen at the
    # first jax import, which happens inside _run_sim/_run_spmd.
    from repro.launch.env import configure_host

    configure_host(verbose=True)
    # Persistent compile cache ($JAX_COMPILATION_CACHE_DIR, else the
    # checkout's .jax_cache): repeat training invocations skip XLA
    # compilation of the chunk/step executables entirely.
    from repro.launch.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    if args.mode == "sim":
        return _run_sim(args)
    return _run_spmd(args)


if __name__ == "__main__":
    raise SystemExit(main())
