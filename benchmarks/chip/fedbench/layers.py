"""Per-layer metrics from the profiler trace of the window.

The traced run profiles at most ``bench.TRACE_CHUNKS`` whole chunks: the
window is the host annotation ``fedbench_window``, which opens in the eval
callback after round ``eval_every`` and closes when ``run_fl`` returns.
From the trace this module keeps, for each device op that overlaps the
window, its interval and its op path, and for each host event its interval
and name.  A device op in the trace carries only its HLO instruction name
(``%fusion.5952 = ...``); its path is that instruction's ``op_name``
metadata in the compiled program the ``XLA Modules`` line says was running
(e.g. ``jit(chunk_fn)/while/body/closed_call/vmap(jit(local_train))/...``;
``bench.keep_programs`` has the programs' HLO).  From those it reduces:

* the device's busy seconds: the union of the op intervals, clipped to the
  window, averaged over the chips;
* each op's interval, so a reader can take the union of the ops whose
  path names a layer;
* the idle gaps between busy intervals, each named by the host events
  (``bench.host_spans``) that overlap it most;

and hands them, with the cell, the peaks and the round count, to each
per-layer metric's reader, ``metrics/<name>.py``: ``read(ctx)`` returns the
value, or None where the cell has nothing for it to read.
"""

from __future__ import annotations

import bisect
import dataclasses
import json
import pathlib
import re

from . import reference, spec
from .bench import WINDOW_EVENT

#: prefix of every op path inside the chunk program's round body
BODY_PREFIX = "jit(chunk_fn)/while/body/"
TOP = 10


@dataclasses.dataclass
class Op:
    chip: int
    name: str
    path: str
    start: int          # ns, on the trace's clock
    end: int


@dataclasses.dataclass
class Context:
    cell: spec.Cell
    rounds: int                  # FL rounds in the window
    chips: int
    window_s: float
    busy_s: float                # averaged over the chips
    ops: list                    # Op, clipped to the window
    peak: dict                   # peaks.json entry of the device kind
    train_flops_per_round: int
    n_sel: int
    pipeline_s: float            # compile pipeline seconds before the window

    def op_seconds(self, marker: str) -> float:
        """Device seconds in which an op whose path contains ``marker`` ran
        (the union of their intervals: a loop's op holds its body's)."""
        return union_seconds([(o.start, o.end) for o in self.ops
                              if marker in o.path])[0] / 1e9


_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.M)
_OP_NAME = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"', re.M)


def op_names(hlo_texts) -> dict:
    """{module name: {instruction: op_name}} from compiled HLO texts."""
    out = {}
    for text in hlo_texts:
        m = _MODULE.search(text)
        if m:
            out[m.group(1)] = dict(_OP_NAME.findall(text))
    return out


def _instruction(event_name: str) -> str:
    return event_name.split(" ", 1)[0].lstrip("%")


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def union_seconds(intervals) -> tuple:
    """(busy ns of the union of ``intervals``, merged [start, end] list)."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def reduce_trace(planes, chips: int, names: dict) -> dict:
    """planes: iterable of (plane name, [(line name, [(event name, start
    ns, end ns, stats dict)])]); names: ``op_names`` of the programs run.
    -> the window, the device ops in it (path: the op's ``op_name``, else
    its instruction name), the host events."""
    window, host, ops = None, [], []
    for pname, lines in planes:
        if pname.startswith("/device:TPU:"):
            try:
                chip = int(pname.split(":")[-1])
            except ValueError:           # e.g. a SparseCore plane
                continue
            if chip >= chips:
                continue
            lines = dict(lines)
            modules = sorted((s, e, n.split("(")[0])
                             for n, s, e, _ in lines.get("XLA Modules", []))
            starts = [m[0] for m in modules]
            for name, s, e, _ in lines.get("XLA Ops", []):
                i = bisect.bisect_right(starts, s) - 1
                module = modules[i][2] if i >= 0 and s < modules[i][1] else ""
                instr = _instruction(name)
                ops.append(Op(chip, instr,
                              names.get(module, {}).get(instr, instr), s, e))
        elif pname.startswith("/host:"):
            for _, events in lines:
                for name, s, e, _ in events:
                    if name == WINDOW_EVENT:
                        window = (s, e)
                    else:
                        host.append((name, s, e))
    if window is None:
        raise RuntimeError(f"no {WINDOW_EVENT} annotation in the trace")
    w0, w1 = window
    clipped = [dataclasses.replace(o, start=max(o.start, w0), end=min(o.end, w1))
               for o in ops if o.end > w0 and o.start < w1]
    return {"window": window, "ops": clipped, "host": host}


def leaf_seconds(ops) -> dict:
    """{op path: device seconds} over the ops that hold no other op (a
    loop's op is left out for its body's)."""
    out = {}
    by_chip = {}
    for o in ops:
        by_chip.setdefault(o.chip, []).append(o)
    for chip_ops in by_chip.values():
        chip_ops.sort(key=lambda o: (o.start, -o.end))
        for i, o in enumerate(chip_ops):
            nxt = chip_ops[i + 1] if i + 1 < len(chip_ops) else None
            if nxt is not None and nxt.start < o.end:
                continue                 # it holds the next op
            out[o.path] = out.get(o.path, 0.0) + (o.end - o.start) / 1e9
    return out


def idle_gaps(merged, window, host) -> list:
    """The longest gaps between busy intervals inside the window, each
    named by the host event name whose events overlap it the most."""
    w0, w1 = window
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    out = []
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        overlap = {}
        for name, s, e in host:
            if e > g0 and s < g1:
                overlap[name] = overlap.get(name, 0) + min(e, g1) - max(s, g0)
        name = max(overlap, key=overlap.get) if overlap else "(no host event)"
        out.append([name, (g1 - g0) / 1e9])
    return out


def load_planes(trace_dir: pathlib.Path):
    import jax

    files = sorted(trace_dir.glob("**/*.xplane.pb"))
    if not files:
        raise RuntimeError(f"no trace under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(str(files[-1]))
    for plane in pd.planes:
        yield plane.name, [
            (line.name, [(e.name, e.start_ns, e.end_ns, _stats(e))
                         for e in line.events])
            for line in plane.lines]


def read(cell: spec.Cell, timed, devs) -> tuple:
    """-> ({metric: value}, busy_s, window_s, breakdown)."""
    from . import data

    chips = len(devs)
    names = op_names(timed.hlo_texts)
    (timed.trace_dir / "op_names.json").write_text(json.dumps(names))
    red = reduce_trace(load_planes(timed.trace_dir), chips, names)
    w0, w1 = red["window"]
    busy_ns, merged = 0, []
    for c in range(chips):
        b, m = union_seconds([(o.start, o.end) for o in red["ops"] if o.chip == c])
        busy_ns += b
        if c == 0:
            merged = m
    peaks = json.loads((spec.HERE / "peaks.json").read_text())
    kind = devs[0].device_kind
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    t = cell.traffic
    n_sel = data.n_selected(t["participation"], t["n_clients"])
    flops = reference.load("flops", cell.model["family"])
    ctx = Context(
        cell=cell, rounds=timed.window_rounds, chips=chips,
        window_s=(w1 - w0) / 1e9, busy_s=busy_ns / chips / 1e9,
        ops=red["ops"], peak=peaks[kind],
        train_flops_per_round=flops.train_per_round(cell.model, t, n_sel),
        n_sel=n_sel, pipeline_s=timed.pipeline_s_before)
    values = {m["name"]: reference.load("metrics", m["name"]).read(ctx)
              for m in cell.per_layer}
    per_op = {}
    for path, sec in leaf_seconds(red["ops"]).items():
        label = path.removeprefix(BODY_PREFIX)[:160]
        per_op[label] = per_op.get(label, 0.0) + sec
    breakdown = {
        "device_ops": sorted(([k, v] for k, v in per_op.items()),
                             key=lambda kv: -kv[1])[:TOP],
        "idle_gaps": idle_gaps(merged, red["window"], red["host"]),
    }
    return values, ctx.busy_s, ctx.window_s, breakdown
