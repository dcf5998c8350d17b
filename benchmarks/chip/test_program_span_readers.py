"""CPU tests of the readers of the program's spans and scopes
(``metrics/input_wait_ms.py``, ``host_assemble_ms.py``, ``server_ms.py``,
through ``fedbench/spans.py``): a hand-built trace in ``reduce_trace``'s
input format, where the values are counted by hand, and a profiler trace
written on the CPU, from which ``spans.events`` reads the window's host
spans."""

from __future__ import annotations

import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from fedbench import bench, layers, reference, spans  # noqa: E402

READERS = ("input_wait_ms", "host_assemble_ms", "server_ms")
BODY = "jit(chunk_fn)/while/body/closed_call/"
NAMES = {"jit_chunk_fn": {
    "fusion.1": BODY + "vmap(jit(local_train))/dot_general",
    "fusion.2": BODY + "fl_aggregate/sub",
    "fusion.3": BODY + "fl_server/add",
    "fusion.4": BODY + "fl_aggregate/reduce_sum"}}


def _chip(ops):
    return [("XLA Modules", [("jit_chunk_fn(1)", 0, 1000, {})]),
            ("XLA Ops", [(f"%{n} = f32[4]{{0}} fusion(...)", s, e, {})
                         for n, s, e in ops])]


def _read(monkeypatch, planes, chips, rounds):
    """{reader: value} on ``planes``, with the context ``layers.read``
    builds and the host events ``spans.events`` would read from them."""
    red = layers.reduce_trace(planes, chips, NAMES)
    w0, w1 = red["window"]
    ctx = layers.Context(
        cell=None, rounds=rounds, chips=chips, window_s=(w1 - w0) / 1e9,
        busy_s=0.0, ops=red["ops"], peak={}, train_flops_per_round=0,
        n_sel=4, pipeline_s=0.0)
    host = spans.window_events(planes)
    monkeypatch.setattr(spans, "events", lambda: host)
    return {n: reference.load("metrics", n).read(ctx) for n in READERS}


def test_readers_on_a_hand_built_trace(monkeypatch):
    """Chip 0 is busy over [100, 370] and [600, 720] of a window [0, 1000]
    ns; one ``fl.assemble`` span starts before the window and ends at 100
    (idle throughout), the other spans [500, 700], half of it in an idle
    gap.  Chip 1 runs one server op, which counts for ``server_ms`` but
    leaves chip 0's idle time alone."""
    planes = [
        ("/device:TPU:0", _chip([("fusion.1", 100, 300),
                                 ("fusion.2", 300, 340),
                                 ("fusion.3", 350, 370),
                                 ("fusion.1", 600, 700),
                                 ("fusion.4", 700, 720)])),
        ("/device:TPU:1", _chip([("fusion.3", 0, 50)])),
        ("/host:CPU", [("main", [
            (bench.WINDOW_EVENT, 0, 1000, {}),
            ("fl.assemble", -50, 100, {"chunk": 4}),
            ("fl.draw", -40, 90, {"chunk": 4, "client": 1}),
            ("fl.assemble", 500, 700, {"chunk": 8}),
            ("fl.draw", 510, 650, {"chunk": 8, "client": 2}),
            ("fl.dispatch", 700, 720, {"chunk": 8})])])]
    got = _read(monkeypatch, planes, 2, rounds=2)
    # idle inside the spans: [0, 100] and [500, 600]
    assert got["input_wait_ms"] == pytest.approx(1e3 * 200e-9 / 2)
    # the spans, clipped to the window: [0, 100] and [500, 700]
    assert got["host_assemble_ms"] == pytest.approx(1e3 * 300e-9 / 2)
    # chip 0: [300, 340], [350, 370], [700, 720]; chip 1: [0, 50]
    assert got["server_ms"] == pytest.approx(1e3 * 130e-9 / 2)


def test_readers_give_none_without_the_programs_names(monkeypatch):
    """A program without the spans and scopes (the parent of this
    instrumentation) leaves each metric out rather than failing."""
    planes = [("/device:TPU:0", _chip([("fusion.1", 100, 300)])),
              ("/host:CPU", [("main", [(bench.WINDOW_EVENT, 0, 1000, {}),
                                       ("draw_batch", 400, 500, {})])])]
    assert _read(monkeypatch, planes, 1, rounds=2) == dict.fromkeys(READERS)


def test_events_from_a_profiler_trace(tmp_path):
    """``spans.events`` reads a trace file's host planes: the window's
    spans, clipped to it, and none from outside it; [] with no trace."""
    import jax

    assert spans.events(tmp_path / "none") == []
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("fl.assemble", chunk=0):
            pass
        with jax.profiler.TraceAnnotation(bench.WINDOW_EVENT):
            with jax.profiler.TraceAnnotation("fl.assemble", chunk=4):
                jax.numpy.ones(4).block_until_ready()
            with jax.profiler.TraceAnnotation("fl.drain", chunk=4):
                pass
    finally:
        jax.profiler.stop_trace()
    names = [n for n, _, _ in spans.events(tmp_path)]
    assert names.count("fl.assemble") == 1 and names.count("fl.drain") == 1
    assert all(s <= e for _, s, e in spans.events(tmp_path))
