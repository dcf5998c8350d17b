"""One run of one cell: set-up, the measured window, and the comparison that
decides ``correct``.

The window drives ``repro.fl.run_fl`` with the fused engine, the way a user
of the simulator runs it.  ``run_fl`` can be watched only at its eval
callbacks, and it returns the ledger only when it ends, so a run makes two
calls in one process:

1. a warm call of ``1 + 2 * eval_every`` rounds: it compiles both chunk
   programs (one round, then ``eval_every`` rounds) and times one steady
   chunk, from which the window's length is sized;
2. the timed call of ``R = start + 1 + eval_every * n`` rounds, where
   ``start`` is the first eval round by which the seed has chosen every
   client once (``window_start``).  Its chunks through round ``start``
   compile from the persistent cache and are set-up; the window runs from
   the callback after round ``start`` to the call's return, so it holds
   ``n`` whole chunks of ``eval_every`` rounds, each ending in the eval
   sync, and it ends with the device done (the host holds every round's
   stats and eval).

``round_ms`` is the window's wall time over the ``n * eval_every`` rounds in
it.  No program may compile inside it: compiles are counted there from
``jax.monitoring`` and compared with the limit 0.

The timed call is also what the comparison reads: its weights after round 0
(the one-round chunk) and after round ``eval_every`` (the first chunk of the
program that the window drives), its eval losses there, and its ledger for
every round.  The reference follows the same seed through those rounds once
the window has closed and the program's state is freed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import os
import pathlib
import statistics
import time

from . import reference, spec

#: monitoring events: one backend compile (or persistent-cache load) per
#: executable, and the whole pipeline that stalls a cold dispatch
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
PIPELINE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    COMPILE_EVENT,
)
#: chunks the traced window covers at most: whole chunks and at least two
#: eval syncs, and a trace small enough to read within the run's time
TRACE_CHUNKS = 3
WINDOW_EVENT = "fedbench_window"
CACHE_DIR = spec.ROOT / ".jax_cache"
TRACE_DIR = spec.ROOT / ".fedbench" / "trace"


class NoChip(RuntimeError):
    pass


class Watcher:
    """Compile events from ``jax.monitoring``: (time received, seconds,
    whether it built or loaded an executable).  A listener cannot be
    unregistered, so a process installs one (``Watcher.install``)."""

    _installed = None

    @classmethod
    def install(cls) -> "Watcher":
        if cls._installed is None:
            cls._installed = cls()
        return cls._installed

    def __init__(self):
        import jax

        self.events = []

        def listen(event, secs, **_):
            if event in PIPELINE_EVENTS:
                self.events.append((time.perf_counter(), float(secs),
                                    event == COMPILE_EVENT))

        jax.monitoring.register_event_duration_secs_listener(listen)

    def between(self, t0: float, t1: float):
        ev = [e for e in self.events if t0 <= e[0] <= t1]
        return sum(e[2] for e in ev), sum(e[1] for e in ev)


def devices(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"this cell needs {chips} TPU chip(s); JAX sees "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def enable_cache(path: pathlib.Path = CACHE_DIR) -> None:
    import jax

    path.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(path)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def fl_config(cell: spec.Cell, seed: int, rounds: int):
    from repro.fl import FLConfig
    from repro.models.config import ArchConfig

    arch_keys = {f.name for f in dataclasses.fields(ArchConfig)}
    arch = ArchConfig(**{k: v for k, v in cell.model.items() if k in arch_keys})
    fl_keys = {f.name for f in dataclasses.fields(FLConfig)}
    kw = {k: v for k, v in cell.traffic.items() if k in fl_keys}
    return FLConfig(**kw, arch=arch, seed=seed, rounds=rounds, engine="fused")


@contextlib.contextmanager
def capture_evals(store: list, n: int):
    """Copies the global weights the program's eval is given, the first
    ``n`` times, to the host -- before the next chunk consumes them."""
    import jax
    from repro.fl import simulation

    original = simulation.make_batched_eval

    def patched(arch):
        fn = original(arch)

        def eval_all(params, block):
            if len(store) < n:
                store.append(jax.device_get(params))
            return fn(params, block)

        return eval_all

    simulation.make_batched_eval = patched
    try:
        yield
    finally:
        simulation.make_batched_eval = original


@contextlib.contextmanager
def host_spans():
    """Names the host's work in the trace (the profiler's Python tracer is
    off, as it would slow the host it measures): each batch the synthetic
    task draws, and each device-to-host fetch (a chunk's stats, an eval)."""
    import jax
    from repro.data import synthetic
    from repro.fl import engine

    draw, fetch = synthetic.SyntheticLMTask.sample_tokens, engine.host_fetch

    def draw_span(*a, **k):
        with jax.profiler.TraceAnnotation("draw_batch"):
            return draw(*a, **k)

    def fetch_span(x):
        with jax.profiler.TraceAnnotation("host_fetch"):
            return fetch(x)

    synthetic.SyntheticLMTask.sample_tokens = draw_span
    engine.host_fetch = fetch_span
    try:
        yield
    finally:
        synthetic.SyntheticLMTask.sample_tokens = draw
        engine.host_fetch = fetch


@contextlib.contextmanager
def keep_programs(store: dict):
    """Records, for the chunk program and the eval, the jitted function and
    the shapes of its last call, so that its compiled HLO -- which names
    each instruction's ``op_name`` -- can be had again after the window
    (``program_texts``): the profiler's device ops carry only instruction
    names."""
    import jax
    from repro.fl import engine, simulation

    build, make_eval = engine._build_chunk, simulation.make_batched_eval

    def shapes(args):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=getattr(a, "sharding", None)),
            args)

    def recorded(fn, name):
        def call(*args):
            store[name] = (fn, shapes(args))     # before donation frees them
            return fn(*args)

        call._cache_size = getattr(fn, "_cache_size", None)
        return call

    engine._build_chunk = lambda *a, **k: recorded(build(*a, **k), "chunk")
    simulation.make_batched_eval = lambda arch: recorded(make_eval(arch), "eval")
    try:
        yield
    finally:
        engine._build_chunk, simulation.make_batched_eval = build, make_eval


def program_texts(store: dict) -> tuple:
    """The compiled HLO text of each recorded program (from the persistent
    cache: the same programs the window ran), and the most device memory
    one of them takes while it runs: its arguments, outputs and
    temporaries, as its memory analysis counts them.  The device's own
    ``peak_bytes_in_use`` counts the buffers JAX holds, not a program's
    temporaries."""
    texts, most = [], 0
    for fn, args in store.values():
        compiled = fn.lower(*args).compile()
        texts.append(compiled.as_text())
        m = compiled.memory_analysis()
        if m is not None:
            most = max(most, m.argument_size_in_bytes + m.output_size_in_bytes
                       + m.temp_size_in_bytes - m.alias_size_in_bytes)
    return texts, most


@dataclasses.dataclass
class Timed:
    result: object           # FLResult of the timed call
    captured: list           # host weights after round 0 and eval_every
    rounds: int              # R
    window_rounds: int
    t_start: float
    t_end: float
    compiles: int            # executables built or loaded in the window
    pipeline_s_before: float  # trace + lower + compile before the window
    trace_dir: pathlib.Path | None
    hlo_texts: list          # compiled programs of the traced window
    program_bytes: int       # the most memory one of them takes


def window_start(cell: spec.Cell, seed: int) -> int:
    """The eval round after which the window opens: the first multiple of
    ``eval_every`` by which the seed's selection chain has chosen every
    client once.  A client's first batch builds its V x V sampling table on
    the host, so a window that opened earlier would hold as many of those
    builds as the seed leaves clients unchosen."""
    from . import data

    t = cell.traffic
    E, C = int(t["eval_every"]), int(t["n_clients"])
    n_sel = data.n_selected(t["participation"], C)
    seen, r = set(), 0
    while True:
        seen.update(data.selected_clients(seed, r, C, n_sel))
        if len(seen) == C:
            return E * max(1, math.ceil(r / E))
        r += 1


def run_program(cell: spec.Cell, seed: int, seconds: float, trace: bool,
                watcher: Watcher) -> Timed:
    import jax
    from repro.fl import run_fl

    E = int(cell.traffic["eval_every"])
    stamps = {}
    run_fl(fl_config(cell, seed, 1 + 2 * E),
           progress=lambda r, _: stamps.setdefault(r, time.perf_counter()))
    chunk_s = max(stamps[2 * E] - stamps[E], 1e-6)
    n = max(1, math.ceil(seconds / chunk_s))
    if trace:
        n = min(max(n, 2), TRACE_CHUNKS)
    start = window_start(cell, seed)
    R = start + 1 + E * n

    captured, marks = [], {}
    stack = contextlib.ExitStack()

    def progress(rnd, _):
        if rnd == start:
            if trace:
                import shutil

                shutil.rmtree(TRACE_DIR, ignore_errors=True)
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 1    # the annotations, not JAX's
                jax.profiler.start_trace(str(TRACE_DIR),
                                         profiler_options=options)
                stack.enter_context(host_spans())
                stack.enter_context(jax.profiler.TraceAnnotation(WINDOW_EVENT))
            marks["start"] = time.perf_counter()

    programs = {}
    with contextlib.ExitStack() as hooks:
        if trace:
            hooks.enter_context(keep_programs(programs))
        hooks.enter_context(capture_evals(captured, 2))
        res = run_fl(fl_config(cell, seed, R), progress=progress)
    t_end = time.perf_counter()
    if trace:
        stack.close()
        jax.profiler.stop_trace()
    compiles, _ = watcher.between(marks["start"], t_end)
    _, before = watcher.between(0.0, marks["start"])
    texts, program_bytes = program_texts(programs) if trace else ([], 0)
    return Timed(res, captured, R, R - 1 - start, marks["start"], t_end,
                 compiles, before, TRACE_DIR if trace else None, texts,
                 program_bytes)


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def worst_leaf(prog: dict, ref: dict) -> float:
    """The largest gap between the program's norm of a leaf's change and the
    reference's, over the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    med = statistics.median(ref.values())
    return max(abs(prog[g] - ref[g]) / max(ref[g], med, 1e-30) for g in ref)


def ledger_gaps(cell: spec.Cell, timed: Timed, groups: dict,
                seed: int) -> list:
    """Per round of the timed call: |charged bits - Formula 14 of what the
    round shipped|, with the updating layers implied by the selection."""
    from . import data

    t = cell.traffic
    method = reference.load("methods", t["method"])
    C = t["n_clients"]
    n_sel = data.n_selected(t["participation"], C)
    seen, gaps = set(), []
    charged = timed.result.ledger.per_round_uplink_bits
    shipped = timed.result.extra["uplink_stats"]
    for r in range(timed.rounds):
        sel = data.selected_clients(seed, r, C, n_sel)
        again = sum(c in seen for c in sel)
        seen.update(sel)
        n_upd = {g: again * L for g, (_, L) in groups.items()}
        stats = {g: (s[1], s[2]) for g, s in shipped[r].items()}
        try:
            want = method.round_bits(groups, t, stats, n_sel, n_upd)
        except KeyError:          # shipped stats for other groups
            want = -1
        gaps.append(abs(int(charged[r]) - want))
    return gaps


@dataclasses.dataclass
class Observed:
    """What one side of the comparison produced: the eval losses and the
    host weights after round 0 and after round ``eval_every``."""
    loss0: float
    lossE: float
    params0: dict
    paramsE: dict


def follow(cell: spec.Cell, seed: int, cd=None):
    """The reference (``cd``: operands of its products rounded to ``cd``,
    the precision control) through rounds ``0 .. eval_every``.  Returns
    (initial host weights, Observed, groups)."""
    import jax

    ref = reference.Run(cell.model, cell.traffic, seed, cd=cd)
    init = jax.device_get(ref.params)
    ref.round(0)
    p0, l0 = jax.device_get(ref.params), ref.eval_loss()
    for r in range(1, int(cell.traffic["eval_every"]) + 1):
        ref.round(r)
    obs = Observed(l0, ref.eval_loss(), p0, jax.device_get(ref.params))
    return init, obs, ref.groups


def gaps(E: int, init: dict, got: Observed, want: Observed) -> dict:
    """The numbers compared between a run (``got``) and the reference."""
    return {
        "loss_r0": abs(got.loss0 - want.loss0) / want.loss0,
        f"loss_r{E}": abs(got.lossE - want.lossE) / want.lossE,
        "upd_r0": worst_leaf(reference.leaf_norms(got.params0, init),
                             reference.leaf_norms(want.params0, init)),
        f"chg_r{E}": worst_leaf(reference.leaf_norms(got.paramsE, init),
                                reference.leaf_norms(want.paramsE, init)),
    }


def compare(cell: spec.Cell, timed: Timed, seed: int) -> dict:
    """The numbers ``correct`` compares: the timed call against a reference
    run of the same seed, its ledger, and the window's compiles."""
    init, want, groups = follow(cell, seed)
    res = timed.result
    got = Observed(res.eval_loss[0], res.eval_loss[1], *timed.captured)
    ledger = ledger_gaps(cell, timed, groups, seed)
    out = gaps(int(cell.traffic["eval_every"]), init, got, want)
    out.update(ledger_bits=max(ledger), window_compiles=timed.compiles,
               _ledger_rounds_off=sum(g != 0 for g in ledger))
    return out


def free_device() -> None:
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


def peak_bytes(devs) -> int:
    """The fullest chip's peak: the buffers JAX held at their peak, and the
    most the runtime reserved at once for a program's temporaries, which
    the TPU counts apart from ``peak_bytes_in_use``."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", 0)
                     + stats.get("peak_bytes_reserved", 0))
    return int(max(peaks))


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, devs,
             t0: float, cache: bool = True) -> dict:
    """One run: the program's window, then the comparison.  Returns the
    result line as a dict, ``checks`` last."""
    from . import layers

    if cache:
        enable_cache()
    watcher = Watcher.install()
    timed = run_program(cell, seed, seconds, trace, watcher)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes(devs)}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    breakdown = None
    if trace:
        values, busy_s, window_s, breakdown = layers.read(cell, timed, devs)
        device.update(busy_s=busy_s, window_s=window_s,
                      program_bytes=timed.program_bytes)
    else:
        values = {
            "round_ms": (timed.t_end - timed.t_start) * 1e3
            / timed.window_rounds,
            "setup_s": timed.t_start - t0,
        }
    metrics = {k: {"value": v, "unit": units[k]}
               for k, v in values.items() if v is not None and k in units}
    free_device()
    numbers = compare(cell, timed, seed)
    unknown = set(cell.limits) - set(numbers)
    if unknown:
        raise KeyError(f"limits for numbers the harness does not read: "
                       f"{sorted(unknown)}")
    # the eval losses are read (readings.py) but compared only where the
    # cell's limits file names them: no fault or control separates them
    checks = {k: {"value": v, "limit": cell.limits[k]}
              for k, v in numbers.items() if k in cell.limits}
    out = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": timed.rounds,
        "failed": numbers["_ledger_rounds_off"],
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
