"""The program's host spans in the traced window, for the readers of
``fl.*`` spans (``metrics/input_wait_ms.py``, ``metrics/host_assemble_ms.py``).

``layers.Context`` hands a reader the window's device ops but not its host
events, so these take them from the trace that ``bench.run_program`` wrote
under ``bench.TRACE_DIR``: the host planes alone, read once per trace file.
A trace of a program that emits no such span gives None, not an error.
"""

from __future__ import annotations

import pathlib

from . import bench, layers

_CACHE: dict = {}


def host_planes(path: pathlib.Path):
    """The host planes of the trace file ``path``, in ``reduce_trace``'s
    input format (the events' stats left out: no reader needs them)."""
    import jax

    pd = jax.profiler.ProfileData.from_file(str(path))
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            yield plane.name, [
                (line.name, [(e.name, e.start_ns, e.end_ns, {})
                             for e in line.events])
                for line in plane.lines]


def window_events(planes) -> list:
    """(name, start ns, end ns) of each host event that overlaps the
    window, clipped to it."""
    red = layers.reduce_trace(planes, 0, {})
    w0, w1 = red["window"]
    return [(n, max(s, w0), min(e, w1)) for n, s, e in red["host"]
            if e > w0 and s < w1]


def events(trace_dir: pathlib.Path = bench.TRACE_DIR) -> list:
    """``window_events`` of the newest trace under ``trace_dir``; [] where
    there is none."""
    files = sorted(trace_dir.glob("**/*.xplane.pb"))
    if not files:
        return []
    key = (str(files[-1]), files[-1].stat().st_mtime_ns)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = window_events(host_planes(files[-1]))
    return _CACHE[key]


def span_seconds(ctx, name: str, idle: bool = False):
    """Seconds of the union of the window's host spans called ``name``;
    with ``idle``, only those in which chip 0 ran no op.  None where the
    trace holds no such span."""
    spans = [(s, e) for n, s, e in events() if n == name]
    if not spans:
        return None
    total, merged = layers.union_seconds(spans)
    if idle:                    # both lists sorted and disjoint
        _, busy = layers.union_seconds([(o.start, o.end) for o in ctx.ops
                                        if o.chip == 0])
        i = j = 0
        while i < len(merged) and j < len(busy):
            (s0, e0), (s1, e1) = merged[i], busy[j]
            total -= max(0, min(e0, e1) - max(s0, s1))
            if e0 < e1:
                i += 1
            else:
                j += 1
    return total / 1e9
