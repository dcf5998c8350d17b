"""Tests of the chip benchmark that run on the CPU.

* every cell of ``BENCHMARK.json`` resolves to its files, and its traffic
  and configuration build the program's ``FLConfig``;
* the FLOP counter against a hand count of one Granite layer;
* the trace reduction (busy union, idle share, op attribution, idle gaps)
  and the readers on a small trace recorded on a TPU v5e;
* the comparison that decides ``correct``: a sound run at a tiny size
  passes, and the run fails with the timed path broken underneath (the
  state left unchanged, half of each batch left out, an answer altered), as
  it does with the precision control in the reference's place.
"""

from __future__ import annotations

import json
import pathlib
import sys
import time

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from fedbench import bench, layers, reference, spec  # noqa: E402

BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
FIX = HERE / "fixtures"


# ---------------------------------------------------------------------------
# files and configurations
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_files_resolve_and_build(workload):
    cell = spec.load(workload)
    cfg = bench.fl_config(cell, seed=2**31 + 7, rounds=9)
    assert cfg.engine == "fused" and cfg.arch.d_model == cell.model["d_model"]
    assert cfg.method == cell.traffic["method"]
    E = cell.traffic["eval_every"]
    assert set(cell.limits) >= {"upd_r0", f"chg_r{E}", "ledger_bits",
                                "window_compiles"}
    assert cell.limits["ledger_bits"] == 0 == cell.limits["window_compiles"]
    for m in cell.per_layer:
        assert callable(reference.load("metrics", m["name"]).read)
    reference.load("flops", cell.model["family"])
    reference.load("models", cell.model["family"])
    reference.load("methods", cell.traffic["method"])


@pytest.mark.parametrize("alone", [False, True])
def test_run_refuses_without_chip_or_program(alone, tmp_path):
    """No result line off the chip (exit 2), nor in a directory that holds
    only BENCHMARK.json and the benchmark's files (exit 1)."""
    import os
    import shutil
    import subprocess

    root = spec.ROOT
    if alone:
        root = tmp_path
        shutil.copy(spec.ROOT / "BENCHMARK.json", root)
        for p in BENCH["paths"]:
            shutil.copytree(spec.ROOT / p, root / p,
                            ignore=shutil.ignore_patterns("__pycache__"))
    cmd = BENCH["command"] + ["--workload", BENCH["workloads"][0]["name"],
                              "--seed", str(2**31 + 3), "--seconds", "1",
                              "--trace", "0"]
    out = subprocess.run(cmd, cwd=root, capture_output=True, text=True,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         timeout=120)
    assert out.returncode == (1 if alone else 2), out.stderr[-2000:]
    assert out.stdout.strip() == ""


def test_configs_keep_published_widths():
    """Every width as the program's preset of the source has it; each key
    in ``reduced`` states its published value and differs from it."""
    from repro.configs import get_config

    for c in BENCH["configs"]:
        model = json.loads((spec.ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(model["reduced"])
        published = get_config(model["program_config"])
        for key in ("d_model", "n_heads", "n_kv_heads", "d_ff", "n_experts",
                    "experts_per_tok"):
            assert key not in model["reduced"]
            assert model[key] == getattr(published, key), key
        for key, cut in model["reduced"].items():
            assert model[key] != cut["published"], key
        for key in ("n_layers", "vocab"):
            assert model["reduced"][key]["published"] == getattr(published, key)


def test_flops_hand_count_granite_layer():
    flops = reference.load("flops", "moe")
    cfg = json.loads((HERE / "configs" / "granite-moe-1b-a400m.2l.json")
                     .read_text())
    one = dict(cfg, n_layers=1, vocab=0)
    S = 512
    # q 1024x1024, k and v 1024x512, o 1024x1024; causal QK and PV over
    # 512*513/2 pairs at 16 heads x 64; router 1024x32; 8 experts of three
    # 1024x512 products; a multiply-add is 2
    hand = (2 * S * (1024 * 1024 * 2 + 1024 * 512 * 2)
            + 2 * 2 * 16 * 64 * (512 * 513 // 2)
            + 2 * S * 1024 * 32
            + 8 * S * 3 * 2 * 1024 * 512)
    assert flops.forward_per_sequence(one, S) == hand
    head = 2 * S * 1024 * 6144
    assert flops.forward_per_sequence(dict(one, vocab=6144), S) == hand + head
    traffic = {"local_steps": 1, "batch": 4, "seq": S}
    assert flops.train_per_round(cfg, traffic, 4) == (
        3 * 4 * 4 * (2 * hand + head))


# ---------------------------------------------------------------------------
# trace reduction and readers
# ---------------------------------------------------------------------------

def test_trace_reduction_on_recorded_trace():
    rec = json.loads((FIX / "trace_v5e.json").read_text())
    planes = [(p["plane"], [(ln["line"], [tuple(e) for e in ln["events"]])
                            for ln in p["lines"]]) for p in rec["planes"]]
    red = layers.reduce_trace(planes, 1, rec["op_names"])
    w0, w1 = red["window"]
    ops = red["ops"]
    assert all(w0 <= o.start <= o.end <= w1 for o in ops)
    busy, merged = layers.union_seconds([(o.start, o.end) for o in ops])
    want = json.loads((FIX / "trace_v5e_expected.json").read_text())
    assert busy == want["busy_ns"]
    assert len(merged) == want["busy_intervals"]
    gaps = layers.idle_gaps(merged, red["window"], red["host"])
    assert [g[0] for g in gaps] == [g[0] for g in want["idle_gaps"]]
    assert [g[1] for g in gaps] == pytest.approx(
        [g[1] for g in want["idle_gaps"]])
    ctx = layers.Context(
        cell=None, rounds=want["rounds"], chips=1, window_s=(w1 - w0) / 1e9,
        busy_s=busy / 1e9, ops=ops, peak={"bf16_flops_per_s": 197e12},
        train_flops_per_round=want["train_flops_per_round"], n_sel=4,
        pipeline_s=12.5)
    for name, value in want["metrics"].items():
        got = reference.load("metrics", name).read(ctx)
        assert (got is None) if value is None else got == pytest.approx(value)


def test_op_names_from_compiled_hlo():
    text = ("HloModule jit_chunk_fn, entry_computation_layout={()->()}\n"
            "  %fusion.12 = f32[4]{0} fusion(%p), kind=kLoop, calls=%c, "
            'metadata={op_name="jit(chunk_fn)/while/body/vmap(jit(local_train))'
            '/dot_general" stack_frame_id=3}\n'
            "  ROOT %tuple.1 = (f32[4]{0}) tuple(%fusion.12)\n")
    names = layers.op_names([text])
    assert names == {"jit_chunk_fn": {
        "fusion.12": "jit(chunk_fn)/while/body/vmap(jit(local_train))/dot_general"}}
    planes = [("/device:TPU:0", [
        ("XLA Modules", [("jit_chunk_fn(123)", 0, 100, {})]),
        ("XLA Ops", [("%while.3 = (f32[4]) while(...)", 5, 60, {}),
                     ("%fusion.12 = f32[4]{0} fusion(...)", 10, 30, {}),
                     ("%fusion.12 = f32[4]{0} fusion(...)", 40, 50, {})])]),
              ("/host:CPU", [("main", [(bench.WINDOW_EVENT, 0, 100, {})])])]
    red = layers.reduce_trace(planes, 1, names)
    assert [o.path.split("/")[-1] for o in red["ops"]] == [
        "while.3", "dot_general", "dot_general"]
    assert layers.leaf_seconds(red["ops"]) == pytest.approx({
        names["jit_chunk_fn"]["fusion.12"]: 30e-9})


def test_union_of_overlapping_and_disjoint_intervals():
    busy, merged = layers.union_seconds([(5, 9), (0, 2), (1, 3), (9, 10)])
    assert busy == 3 + 5 and merged == [[0, 3], [5, 10]]
    host = [("draw_batch", 11, 14), ("draw_batch", 15, 19),
            ("host_fetch", 3, 4), ("host_fetch", 19, 21)]
    gaps = layers.idle_gaps(merged, (0, 20), host)
    assert gaps == [["draw_batch", 10e-9], ["host_fetch", 2e-9]]


# ---------------------------------------------------------------------------
# the comparison, at a tiny size on the CPU
# ---------------------------------------------------------------------------

SEED = 2**31 + 11


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A one-layer MoE under FedAvg and its limits; the compile cache in a
    directory of the test's own, so the runs after the first load their
    programs."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    old = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("jax_cache")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    yield spec.Cell(
        "tiny", 1, json.loads((FIX / "tiny-moe.json").read_text()),
        json.loads((FIX / "tiny-traffic.json").read_text()),
        json.loads((FIX / "tiny-limits.json").read_text()),
        [{"name": "round_ms", "unit": "ms"}, {"name": "setup_s", "unit": "s"}],
        [])
    jax.config.update("jax_compilation_cache_dir", old[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old[1])
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def reference_run(tiny):
    """The reference of the tiny cell's seed, followed once: the faults below
    break the program, not the reference."""
    return bench.follow(tiny, SEED)


def _run(cell, reference_run, monkeypatch):
    """One run of the cell with the reference given; the in-process caches
    kept between runs, which a run on the chip clears before its reference,
    so that each run here does not trace every program again."""
    import gc

    monkeypatch.setattr(bench, "follow", lambda *a, **k: reference_run)
    monkeypatch.setattr(bench, "free_device", gc.collect)
    devs = bench.devices(1, require_tpu=False)
    return bench.run_cell(cell, SEED, 0.05, False, devs, time.perf_counter(),
                          cache=False)


def test_sound_run_is_correct(tiny, reference_run, monkeypatch):
    out = _run(tiny, reference_run, monkeypatch)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["checks"]["window_compiles"]["value"] == 0


def _wrap_chunk(monkeypatch, transform):
    """Breaks the timed path underneath: every chunk program's call goes
    through ``transform(fn, args)``."""
    from repro.fl import engine

    build = engine._build_chunk

    def broken(*a, **k):
        fn = build(*a, **k)

        def chunk(*args):
            return transform(fn, args)

        chunk._cache_size = fn._cache_size
        return chunk

    monkeypatch.setattr(engine, "_build_chunk", broken)


def _state_unchanged(monkeypatch):
    import jax
    import jax.numpy as jnp

    def keep_params(fn, args):
        kept = jax.tree.map(jnp.copy, args[0])
        return (kept,) + tuple(fn(*args)[1:])

    _wrap_chunk(monkeypatch, keep_params)


def _half_batch(monkeypatch):
    def first_half_twice(fn, args):
        batches = {k: v.at[..., v.shape[-2] // 2:, :].set(
            v[..., : v.shape[-2] - v.shape[-2] // 2, :])
            for k, v in args[5].items()}
        return fn(*args[:5], batches, *args[6:])

    _wrap_chunk(monkeypatch, first_half_twice)


def _answer_altered(monkeypatch):
    from repro.fl import compression

    consume = compression.RoundAccountant.consume

    def off_by_one_scalar(self, packed, ledger, rnd):
        consume(self, packed, ledger, rnd)
        if rnd == 1:                       # one more f32 charged in round 1
            ledger.charge_uplink_bits(32, round_idx=rnd)

    monkeypatch.setattr(compression.RoundAccountant, "consume",
                        off_by_one_scalar)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered])
def test_broken_timed_path_is_not_correct(tiny, reference_run, fault,
                                          monkeypatch):
    fault(monkeypatch)
    assert not _run(tiny, reference_run, monkeypatch)["correct"]


def test_precision_control_is_not_correct(tiny, reference_run):
    import jax.numpy as jnp

    E = tiny.traffic["eval_every"]
    init, want, _ = reference_run
    _, ctl, _ = bench.follow(tiny, SEED, cd=jnp.float8_e4m3fn)
    got = bench.gaps(E, init, ctl, want)
    assert any(got[k] > tiny.limits[k] for k in got if k in tiny.limits)
