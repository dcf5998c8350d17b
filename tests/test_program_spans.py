"""The fused engine's host spans and device scopes (DESIGN.md "Tracing").

One small FedAvg run under ``jax.profiler.trace``: every span of the host
loop is in the trace with its arguments, in the counts the run implies, and
no span holds a whole chunk.  The compiled chunk program names the round
body's phases in its ``op_name`` metadata, where a trace reader finds them.
The run goes through the four names a profiling harness wraps from outside
(``engine.host_fetch``, ``SyntheticLMTask.sample_tokens``,
``engine._build_chunk``, ``simulation.make_batched_eval``), so a rename or a
changed call path fails here.
"""

import jax
import pytest

from repro.core import metrics
from repro.data import synthetic
from repro.fl import FLConfig, engine, run_fl, simulation
from repro.fl.engine import plan_chunks
from repro.models.config import ArchConfig

_ARCH = ArchConfig(name="span-check", family="dense", n_layers=1,
                   d_model=32, n_heads=2, n_kv_heads=2, d_ff=64, vocab=64,
                   dtype="float32", remat=False, attn_chunk=0)

#: chunks (0,1) (1,3) (3,5): two lengths, the third chunk compiles nothing
_CFG = dict(arch=_ARCH, rounds=5, n_clients=3, participation=2 / 3,
            local_steps=2, batch=2, seq=8, eval_every=2, scan_rounds=2,
            min_params=2048, seed=3, use_pallas=False)


def _host_spans(trace_dir):
    """[(name, start ns, end ns, {argument: value})] of the fl.* spans."""
    (path,) = trace_dir.glob("**/*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    return [(e.name, e.start_ns, e.end_ns, {k: v for k, v in e.stats})
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("fl.")]


def _compiled_text(program) -> str:
    fn, args = program
    return fn.lower(*args).compile().as_text()


def _record(store, monkeypatch):
    """Wraps the four names, counting calls; ``_build_chunk``'s program is
    kept with the shapes of its last call, and exposes only
    ``_cache_size``, as a harness's wrapper does."""
    calls = dict.fromkeys(("host_fetch", "sample_tokens", "build_chunk",
                           "make_batched_eval"), 0)
    fetch = engine.host_fetch
    draw = synthetic.SyntheticLMTask.sample_tokens
    build = engine._build_chunk
    make_eval = simulation.make_batched_eval

    def counted(name, fn):
        def call(*a, **k):
            calls[name] += 1
            return fn(*a, **k)
        return call

    def recorded_build(*a, **k):
        calls["build_chunk"] += 1
        fn = build(*a, **k)

        def chunk(*args):
            store["chunk"] = (fn, jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args))
            return fn(*args)

        chunk._cache_size = fn._cache_size
        return chunk

    monkeypatch.setattr(engine, "host_fetch", counted("host_fetch", fetch))
    monkeypatch.setattr(synthetic.SyntheticLMTask, "sample_tokens",
                        counted("sample_tokens", draw))
    monkeypatch.setattr(engine, "_build_chunk", recorded_build)
    monkeypatch.setattr(simulation, "make_batched_eval",
                        counted("make_batched_eval", make_eval))
    return calls


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    trace_dir = tmp_path_factory.mktemp("trace")
    store = {}
    with pytest.MonkeyPatch.context() as mp:
        calls = _record(store, mp)
        metrics.reset_host_sync_count()
        # the annotations only: the Python tracer would multiply the
        # trace's size and the time to write it
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        with jax.profiler.trace(str(trace_dir), profiler_options=options):
            res = run_fl(FLConfig(method="fedavg", **_CFG))
        syncs = metrics.host_sync_count()
    return res, _host_spans(trace_dir), syncs, calls, store


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def test_every_span_is_in_the_trace(traced):
    _, spans, _, _, _ = traced
    assert {s[0] for s in spans} == {
        "fl.setup", "fl.assemble", "fl.draw", "fl.dispatch", "fl.drain",
        "fl.eval", "fl.host_fetch"}
    (setup,) = _named(spans, "fl.setup")
    assert setup[3]["clients"] == _CFG["n_clients"]


def test_span_counts_and_chunk_arguments(traced):
    res, spans, syncs, _, _ = traced
    chunks = plan_chunks(_CFG["rounds"], _CFG["eval_every"],
                         _CFG["scan_rounds"])
    starts = [s for s, _ in chunks]
    n_sel = 2
    for name in ("fl.assemble", "fl.dispatch", "fl.drain"):
        got = sorted(_named(spans, name), key=lambda s: s[1])
        assert [s[3]["chunk"] for s in got] == starts, name
    for s, (c0, c1) in zip(sorted(_named(spans, "fl.assemble"),
                                  key=lambda s: s[1]), chunks):
        assert s[3]["rounds"] == c1 - c0
        assert s[3]["batches"] == (c1 - c0) * n_sel * _CFG["local_steps"]
    draws = _named(spans, "fl.draw")
    assert len(draws) == _CFG["rounds"] * n_sel * _CFG["local_steps"]
    assert {d[3]["chunk"] for d in draws} == set(starts)
    assert sorted(s[3]["round"] for s in _named(spans, "fl.eval")) \
        == res.eval_rounds
    fetches = _named(spans, "fl.host_fetch")
    assert len(fetches) == syncs == len(chunks) + len(res.eval_rounds)
    assert all(f[3]["bytes"] > 0 for f in fetches)
    dispatch = sorted(_named(spans, "fl.dispatch"), key=lambda s: s[1])
    new = [s[3]["new_programs"] for s in dispatch]
    assert sum(new) == res.extra["chunk_compiles"] == 2
    assert new[-1] == 0                  # the third chunk repeats a length


def test_no_span_holds_an_assembly(traced):
    """A span that held a whole chunk iteration or the run would hold the
    chunk's assembly: a reader that names an idle gap after the host span
    overlapping it most would then give every gap that one name."""
    _, spans, _, _, _ = traced
    for a in _named(spans, "fl.assemble"):
        for s in spans:
            if s is not a:
                assert not (s[1] <= a[1] and a[2] <= s[2]), (s, a)


def test_next_chunk_is_assembled_before_the_eval_sync(traced):
    """Every chunk here ends in an eval, so each assembly but the first
    runs while the chunk before it is in flight: after its dispatch and
    before its eval, never after the eval's fetch has drained the device."""
    res, spans, _, _, _ = traced
    chunks = plan_chunks(_CFG["rounds"], _CFG["eval_every"],
                         _CFG["scan_rounds"])
    assert [e - 1 for _, e in chunks] == res.eval_rounds
    assemblies = sorted(_named(spans, "fl.assemble"), key=lambda s: s[1])
    evals = {s[3]["round"]: s for s in _named(spans, "fl.eval")}
    dispatches = {s[3]["chunk"]: s for s in _named(spans, "fl.dispatch")}
    assert [a[3]["in_flight"] for a in assemblies] \
        == [0] + [1] * (len(chunks) - 1)
    assert assemblies[0][2] <= dispatches[chunks[0][0]][1]
    for a, (prev_start, prev_end) in zip(assemblies[1:], chunks):
        assert dispatches[prev_start][2] <= a[1]
        assert a[2] <= evals[prev_end - 1][1], (a, evals[prev_end - 1])


def test_wrapped_names_keep_their_call_paths(traced):
    res, _, syncs, calls, _ = traced
    assert calls["host_fetch"] == syncs
    assert calls["sample_tokens"] > 0
    assert calls["build_chunk"] == 1
    assert calls["make_batched_eval"] == 1
    assert engine.host_fetch is metrics.host_fetch


def test_round_body_scopes_reach_the_compiled_program(traced):
    _, _, _, _, store = traced
    text = _compiled_text(store["chunk"])
    for scope in (engine.AGGREGATE_SCOPE, engine.SERVER_SCOPE):
        assert f"/{scope}/" in text, scope
    assert f"/{engine.ENCODE_SCOPE}/" not in text     # FedAvg has no codec
    assert "jit(local_train)" in text


def test_encode_scope_in_a_codec_program(monkeypatch):
    store = {}
    _record(store, monkeypatch)
    run_fl(FLConfig(method="topk", **dict(_CFG, rounds=1)))
    text = _compiled_text(store["chunk"])
    for scope in (engine.ENCODE_SCOPE, engine.AGGREGATE_SCOPE,
                  engine.SERVER_SCOPE):
        assert f"/{scope}/" in text, scope
