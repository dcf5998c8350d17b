#!/usr/bin/env python3
"""Runs one cell of the federated-round benchmark once, on the chip it is
started on, and prints one JSON line of results last.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

``--trace 0`` prints the cell's end-to-end metrics (``round_ms``,
``setup_s``); ``--trace 1`` profiles a window of at most three chunks and
prints the per-layer metrics, read by ``metrics/<name>.py``, with the
device's busy and window seconds and a breakdown of device time and idle
gaps.  Either run compares what the timed call produced with the plain
reference and prints each number beside its limit, as the last lines of
standard error and under ``checks``, the last key of the JSON line.

Exits 2 and prints no result when JAX finds no TPU, or fewer chips than the
cell asks for, and 1 when the checkout lacks the program or a cell file.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import pathlib  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from fedbench import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import repro  # noqa: F401  -- the system under test
        cell = spec.load(args.workload)
    except (ImportError, FileNotFoundError, KeyError) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    from fedbench import bench
    try:
        devs = bench.devices(cell.chips, require_tpu=True)
    except bench.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    out = bench.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                         devs, T0)
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
