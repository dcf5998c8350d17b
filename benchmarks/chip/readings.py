#!/usr/bin/env python3
"""The readings the limits in ``limits/<workload>.json`` are set from, for
many seeds in one process (the benchmark's own runs never run this).

For each seed: the program's timed path through its first chunk of the
window's program (``run_fl`` with ``1 + eval_every`` rounds: the one-round
chunk, then one ``eval_every``-round chunk) against the reference -- the
lower readings -- and, with ``--control``, the precision control (the
reference with every product's operands rounded to float8_e4m3fn, the
step below the configuration's bfloat16) put in the program's place --
the upper readings; with ``--half-batch``, the reference with half of
each batch left out in the program's place, a fault's readings.

    python3 benchmarks/chip/readings.py --workload <name> --seeds 1 2 3 \
        [--control] [--half-batch] [--skip-program]

Prints one JSON line per seed and side.  Needs the chip the cell names.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from fedbench import bench, reference, spec  # noqa: E402


def program_side(cell, seed):
    """The timed call's first ``1 + eval_every`` rounds, as a run makes them;
    also the program's initial weights, to show that the reference draws the
    same ones."""
    import jax
    from repro.fl import run_fl
    from repro.models import model

    E = int(cell.traffic["eval_every"])
    captured, inits = [], []
    init_params = model.init_params

    def keep(*a):
        params = init_params(*a)
        inits.append(jax.device_get(params))   # before a chunk consumes it
        return params

    model.init_params = keep
    try:
        with bench.capture_evals(captured, 2):
            res = run_fl(bench.fl_config(cell, seed, 1 + E))
    finally:
        model.init_params = init_params
    obs = bench.Observed(res.eval_loss[0], res.eval_loss[1], *captured)
    return obs, inits[-1]


class HalfBatch(reference.Run):
    """The reference with half of each batch left out: its second half
    repeats the first, so each step's mean is over the rest."""

    def _batches(self, client):
        b = super()._batches(client)
        half = b["tokens"].shape[-2] // 2
        return {k: v.at[..., half:, :].set(v[..., :half, :])
                for k, v in b.items()}


def follow_half_batch(cell, seed):
    import jax

    run = HalfBatch(cell.model, cell.traffic, seed)
    run.round(0)
    p0, l0 = jax.device_get(run.params), run.eval_loss()
    for r in range(1, int(cell.traffic["eval_every"]) + 1):
        run.round(r)
    return bench.Observed(l0, run.eval_loss(), p0, jax.device_get(run.params))


def worst(init, got, want):
    """The leaf behind each worst-leaf number, with both norms."""
    from fedbench import reference

    out = {}
    for tag, a, b in (("upd", got.params0, want.params0),
                      ("chg", got.paramsE, want.paramsE)):
        pa, pb = reference.leaf_norms(a, init), reference.leaf_norms(b, init)
        med = sorted(pb.values())[len(pb) // 2]
        leaf = max(pb, key=lambda g: abs(pa[g] - pb[g]) / max(pb[g], med))
        out[tag] = [leaf, pa[leaf], pb[leaf], med]
    return out


def main(argv=None) -> int:
    import jax.numpy as jnp

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--skip-program", action="store_true")
    ap.add_argument("--half-batch", action="store_true",
                    help="also read the fault: half of each batch left out")
    ap.add_argument("--cpu", action="store_true",
                    help="allow a run off the chip (a rehearsal, not a reading)")
    args = ap.parse_args(argv)
    cell = spec.load(args.workload)
    try:
        bench.devices(cell.chips, require_tpu=not args.cpu)
    except bench.NoChip as e:
        print(f"readings.py: {e}", file=sys.stderr)
        return 2
    bench.enable_cache()
    E = int(cell.traffic["eval_every"])
    for seed in args.seeds:
        t = time.perf_counter()
        if not args.skip_program:
            got, prog_init = program_side(cell, seed)
            bench.free_device()
        init, want, _ = bench.follow(cell, seed)
        if not args.skip_program:
            same = sum(bool((reference.get(prog_init, g) == reference.get(init, g)).all())
                       for g in reference.groups_of(init))
            print(json.dumps({"seed": seed, "side": "program",
                              **bench.gaps(E, init, got, want),
                              "init_leaves_equal": same,
                              "worst": worst(init, got, want),
                              "s": time.perf_counter() - t}), flush=True)
        if args.control:
            _, ctl, _ = bench.follow(cell, seed, cd=jnp.float8_e4m3fn)
            print(json.dumps({"seed": seed, "side": "control",
                              **bench.gaps(E, init, ctl, want),
                              "worst": worst(init, ctl, want),
                              "s": time.perf_counter() - t}), flush=True)
        if args.half_batch:
            half = follow_half_batch(cell, seed)
            print(json.dumps({"seed": seed, "side": "half_batch",
                              **bench.gaps(E, init, half, want),
                              "worst": worst(init, half, want),
                              "s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
