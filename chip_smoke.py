#!/usr/bin/env python3
"""Smoke run of the federated round on a TPU: one chip, or four.

Drives ``repro.fl.run_fl`` -- the path ``python -m repro.launch.train
--mode sim`` takes -- on fl-tiny (``fl.simulation.default_tiny_arch``:
4 layers, d_model 128, d_ff 512, ~1.6M parameters, weights from the seed)
at its full width, in one process:

  * kernels: each wire and segment kernel of the FL path, compiled for the
    chip, against its ``kernels/ref.py`` oracle on an fl-tiny-sized input;
  * methods: each Table III method, plus GradESTC and SVDFed with the int8
    coefficient wire, through the fused engine and then the reference loop
    on the same config.  ``use_pallas`` is left on auto and must resolve to
    True; eval losses must be finite and within ``LOSS_TOL`` between
    engines; the per-round uplink ledger must be identical between engines
    (for GradESTC, whose charge follows the trajectory, see
    ``DATA_DEPENDENT``); the chunk program of every method with a kernel
    must hold a ``tpu_custom_call``.

``--chips 4`` runs only the sharded engine: GradESTC and FedPAQ with
``devices=4`` against ``devices=1``, with the same checks and the client
axis shown to span four devices.

Every line but the last describes one phase or method; the per-method
times are a smoke record, not a benchmark.  The last line is one JSON
object, ``{"ok": true, "device": {"platform", "kind", "count"}}``.  The
script exits non-zero, without that line, when JAX finds no TPU, when it
runs outside this repository, or when any check fails.

Usage:  python chip_smoke.py [--chips 4]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent

#: (label, method, method_kw) -- the seven Table III methods, then the
#: int8 coefficient wire of the two basis methods
METHODS = [
    ("fedavg", "fedavg", {}),
    ("topk", "topk", {}),
    ("fedpaq", "fedpaq", {}),
    ("signsgd", "signsgd", {}),
    ("fedqclip", "fedqclip", {}),
    ("svdfed", "svdfed", {}),
    ("gradestc", "gradestc", {}),
    ("gradestc-int8", "gradestc", {"wire_dtype": "int8"}),
    ("svdfed-int8", "svdfed", {"wire_dtype": "int8"}),
]
#: methods whose codecs run a Pallas kernel under use_pallas
KERNEL_METHODS = {"fedpaq", "signsgd", "fedqclip", "gradestc",
                  "gradestc-int8", "svdfed-int8"}

#: GradESTC's uplink charge depends on the trajectory: each round a client
#: ships the d_r basis vectors that won its top-k.  The engines compared
#: here (fused vs loop, 4 devices vs 1) are different XLA programs, and on
#: the TPU they round the same f32 work differently (bf16 MXU passes,
#: fusion-dependent reduction orders); after a few rounds a near-tied
#: top-k can go either way, and the two runs ship different d_r from round
#: 3 on.  So GradESTC is held exactly to what does not depend on the
#: trajectory: both runs update the same layers in every round and charge
#: each round exactly Formula 14 of the d_r they shipped
#: (``formula14_bits``), and they charge the same in the first
#: ``IDENTICAL_ROUNDS`` rounds, as every chip run so far has shown (fused
#: and loop parted at round 3 or 4, devices=4 and 1 at round 3).  Every
#: other method -- SVDFed, whose refits are data-dependent too, included --
#: must charge the same in every round.
DATA_DEPENDENT = {"gradestc", "gradestc-int8"}
IDENTICAL_ROUNDS = 3

#: Eval-loss tolerance between two such programs: on the chip their eval
#: losses after 10 rounds of fl-tiny differed by at most 2.3e-4 nats (two
#: runs, all methods); 1e-3 keeps four times that on a loss of ~5.6 nats.
LOSS_TOL = 1e-3


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def check(cond, msg: str) -> None:
    if not cond:
        fail(msg)


def fl_config(method: str, method_kw: dict, devices: int = 1):
    from repro.fl import FLConfig

    # rounds 0 and 9 evaluate; chunks (0,1) (1,5) (5,9) (9,10) run two
    # distinct scan lengths, each compiled once
    return FLConfig(method=method, method_kw=dict(method_kw), rounds=10,
                    n_clients=10, local_steps=4, batch=16, seq=64,
                    eval_every=9, scan_rounds=4, seed=0,
                    devices=devices if devices > 1 else None)


# ---------------------------------------------------------------------------
# kernels vs oracles on the chip
# ---------------------------------------------------------------------------

def kernel_phase() -> None:
    """Compiled kernels against the ref.py oracles.  Integer work (bit
    packing, signs, maxima, scales) must match exactly.  Mosaic may round a
    division or an f32 GEMM differently from XLA, so a quantizer code may
    sit one step off the oracle's and a GEMM output may differ at bf16
    level (relative error at most 1e-2); the line printed says how many
    codes differed and the largest relative GEMM error."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    t0 = time.perf_counter()
    rng = np.random.default_rng(0)
    n = 65536                                  # one fl-tiny attention group
    g = jnp.asarray(rng.standard_normal(n), jnp.float32)

    wk, sk = ops.sign_wire(g)
    wo, so = ops.sign_wire(g, use_kernel=False)
    check(np.array_equal(np.asarray(wk), np.asarray(wo)), "sign words")
    check(float(sk) == float(so), "sign scale")
    check(np.array_equal(np.asarray(ops.sign_unwire(wk, sk, n)),
                         np.asarray(ref.sign_unpack_ref(wk, sk, n))),
          "sign unpack")

    code_diffs = {}                   # codes off the oracle's, per wire
    key = jax.random.PRNGKey(1)
    for bits in (2, 4, 8):
        wk, sk, pad = ops.block_quant_wire(g, key, bits=bits)
        wo, so, _ = ops.block_quant_wire(g, key, bits=bits, use_kernel=False)
        check(np.array_equal(np.asarray(sk), np.asarray(so)),
              f"quant scales bits={bits}")
        rk = np.asarray(ops.block_dequant_wire(wk, sk, pad, bits=bits))
        check(np.array_equal(rk, np.asarray(ops.block_dequant_wire(
            wk, sk, pad, bits=bits, use_kernel=False))),
            f"unpack of kernel words bits={bits}")
        ro = np.asarray(ops.block_dequant_wire(wo, so, pad, bits=bits,
                                               use_kernel=False))
        step = np.repeat(np.asarray(so), 512)[:n] / ((1 << (bits - 1)) - 1)
        check((np.abs(rk - ro) <= step * 1.0001).all(),
              f"quant codes within one step bits={bits}")
        code_diffs[f"quant{bits}"] = int((rk != ro).sum())

    l, k, m = 512, 32, 1536             # an fl-tiny MLP basis, 3 scale blocks
    M = jnp.asarray(np.linalg.qr(rng.standard_normal((l, k)))[0], jnp.float32)
    G = jnp.asarray(rng.standard_normal((l, m)), jnp.float32)
    gemm_rel = {}                     # relative error of each GEMM output

    def close(a, b, what):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        rel = np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)
        check(rel <= 1e-2, f"{what}: relative error {rel:.3g}")
        gemm_rel[what] = rel

    def codes_within_one(a, b, what):
        d = np.abs(np.asarray(a, np.int32) - np.asarray(b, np.int32))
        check(d.max() <= 1, f"{what} codes within one step")
        code_diffs[what] = int((d > 0).sum())

    A, E = ops.encode(M, G)
    Ao, Eo = ref.encode_ref(M, G)
    close(A, Ao, "encode_A")
    close(E, Eo, "encode_E")
    close(ops.decode(M, Ao), ref.decode_ref(M, Ao), "decode")
    ck, sk, hk = ops.coeff_quant(Ao)
    co, so, _ = ops.coeff_quant(Ao, use_kernel=False)
    check(np.array_equal(np.asarray(sk), np.asarray(so)), "coeff scales")
    codes_within_one(ck, co, "coeff")
    check(np.array_equal(np.asarray(hk),
                         np.asarray(ref.coeff_dequant_ref(ck, sk))),
          "coeff ship == dequant(codes)")
    ck, sk, Ek = ops.encode_quant(M, G)
    co, so, Eo = ref.encode_quant_ref(M, G)
    codes_within_one(ck, co, "encode_quant")
    close(Ek, Eo, "encode_quant_E")
    close(ops.decode_wire(M, ck, sk),
          ref.decode_ref(M, ref.coeff_dequant_ref(ck, sk)), "decode_wire")
    print("kernels: ok ({:.1f} s; sign + quant bits 2/4/8 at n={}, segment "
          "kernels at l={} k={} m={}) codes_off_oracle {} max_gemm_rel {:.3g} "
          "({})".format(
              time.perf_counter() - t0, n, l, k, m,
              " ".join(f"{w}={c}" for w, c in code_diffs.items()),
              max(gemm_rel.values()),
              " ".join(f"{w}={r:.3g}" for w, r in gemm_rel.items())),
          flush=True)


# ---------------------------------------------------------------------------
# one method through run_fl
# ---------------------------------------------------------------------------

def _custom_calls(dump_dir: pathlib.Path) -> int:
    """tpu_custom_call ops in the chunk programs the fused run compiled."""
    return sum(p.read_text().count("tpu_custom_call")
               for p in dump_dir.glob("*chunk_fn*"))


def formula14_bits(stats: dict, cfg) -> int:
    """One round's uplink bits of a GradESTC run, recomputed from the
    per-group stats it shipped (``extra["uplink_stats"]``: max d_r, updating
    layers, sum of d_r, d).  Per compressed group (GradESTC, Formula 14):
    a layer that initializes ships its k x l basis and its coefficients, an
    updating layer its coefficients plus each entering vector with its
    index; groups the policy leaves uncompressed ship raw f32."""
    from repro.core.policy import make_policy
    from repro.fl.simulation import default_tiny_arch
    from repro.models import param_group_shapes

    policy = make_policy(param_group_shapes(cfg.arch or default_tiny_arch()),
                         overrides=cfg.policy_overrides,
                         coverage_target=cfg.coverage_target,
                         min_params=cfg.min_params)
    n_sel = max(1, int(round(cfg.participation * cfg.n_clients)))
    int8 = cfg.method_kw.get("wire_dtype", "f32") == "int8"
    check(set(stats) == {p for p, pl in policy.plans.items() if pl.compress},
          f"stats for {sorted(stats)}")
    bits = 0
    for path, pl in policy.plans.items():
        if not pl.compress:
            bits += 32 * pl.n * pl.stack * n_sel
            continue
        _, n_upd, sum_dr, _ = stats[path]
        coeff = pl.k * pl.m * (8 if int8 else 32)
        if int8:                      # one f32 scale per row and 512 columns
            coeff += 32 * pl.k * -(-pl.m // 512)
        n_init = n_sel * pl.stack - n_upd
        bits += (n_init * 32 * pl.k * pl.l + n_sel * pl.stack * coeff
                 + 32 * sum_dr * (pl.l + 1))
    return bits


def compare_ledgers(label: str, cfg, a, b, name_a: str, name_b: str) -> str:
    """Hold two runs' per-round uplink bits to each other (see
    DATA_DEPENDENT); returns "exact" or, for GradESTC, where the shipped
    d_r parted and their sums."""
    ua, ub = a.ledger.per_round_uplink_bits, b.ledger.per_round_uplink_bits
    msg = f"{label}: per-round uplink bits {name_a} {ua} vs {name_b} {ub}"
    check(len(ua) == cfg.rounds == len(ub), msg)
    if label in DATA_DEPENDENT:
        for res, name in ((a, name_a), (b, name_b)):
            want = [formula14_bits(st, cfg) for st in res.extra["uplink_stats"]]
            check(res.ledger.per_round_uplink_bits == want,
                  f"{label} {name}: ledger {res.ledger.per_round_uplink_bits}"
                  f" vs Formula 14 of its shipped d_r {want}")
    if ua == ub:
        return "exact"
    check(label in DATA_DEPENDENT, msg)
    check(ua[:IDENTICAL_ROUNDS] == ub[:IDENTICAL_ROUNDS],
          f"{msg} (differ before round {IDENTICAL_ROUNDS})")
    sa, sb = a.extra["uplink_stats"], b.extra["uplink_stats"]
    check([{p: s[p][1] for p in s} for s in sa]
          == [{p: s[p][1] for p in s} for s in sb],
          f"{label}: updating layers differ between {name_a} and {name_b}")
    part = next(r for r, (x, y) in enumerate(zip(ua, ub)) if x != y)
    dr_a = sum(s[p][2] for s in sa for p in s)
    dr_b = sum(s[p][2] for s in sb for p in s)
    return (f"formula14-exact,parted@round{part},sum_dr={dr_a}/{dr_b}"
            f"({name_a}/{name_b})")


def run_method(label: str, method: str, method_kw: dict, watcher) -> None:
    import jax

    from repro.fl import run_fl

    cfg = fl_config(method, method_kw)
    dump = pathlib.Path(tempfile.mkdtemp(prefix=".smoke_ir_", dir=ROOT))
    try:
        jax.config.update("jax_dump_ir_to", str(dump))
        mark = watcher.snapshot()
        fused = run_fl(cfg)
        _, compile_s = watcher.since(mark)
        calls = _custom_calls(dump)
    finally:
        jax.config.update("jax_dump_ir_to", "")
        shutil.rmtree(dump, ignore_errors=True)
    loop = run_fl(dataclasses.replace(cfg, engine="loop"))

    for res, eng in ((fused, "fused"), (loop, "loop")):
        check(res.extra["engine"] == eng, f"{label}: engine {eng}")
        check(res.extra["use_pallas"] is True,
              f"{label} {eng}: use_pallas resolved False on the chip")
        check(all(math.isfinite(x) for x in res.eval_loss),
              f"{label} {eng}: eval loss {res.eval_loss}")
    ledger = compare_ledgers(label, cfg, fused, loop, "fused", "loop")
    check(fused.eval_rounds == loop.eval_rounds, f"{label}: eval rounds")
    dloss = max(abs(a - b) for a, b in zip(fused.eval_loss, loop.eval_loss))
    check(dloss <= LOSS_TOL,
          f"{label}: eval loss fused {fused.eval_loss} vs loop "
          f"{loop.eval_loss} (|d| {dloss:.3g} > {LOSS_TOL})")
    check(fused.extra["chunk_compiles"] == fused.extra["chunk_shapes"],
          f"{label}: {fused.extra['chunk_compiles']} chunk executables for "
          f"{fused.extra['chunk_shapes']} chunk lengths")
    if label in KERNEL_METHODS:
        check(calls > 0, f"{label}: no tpu_custom_call in the chunk program")
    print(f"method {label}: compile_s={compile_s:.1f} "
          f"uplink_bytes={fused.ledger.uplink_total:.0f} "
          f"ledger={ledger} tpu_custom_calls={calls} "
          f"eval_loss={fused.eval_loss[-1]:.6f} "
          f"loop_eval_loss={loop.eval_loss[-1]:.6f} max_dloss={dloss:.3g}",
          flush=True)


# ---------------------------------------------------------------------------
# the sharded engine on four chips
# ---------------------------------------------------------------------------

def sharded_phase() -> None:
    from repro.fl import run_fl

    for method in ("gradestc", "fedpaq"):
        t0 = time.perf_counter()
        cfg = fl_config(method, {})
        one = run_fl(cfg)
        four = run_fl(fl_config(method, {}, devices=4))
        check(four.extra["devices"] == 4 and one.extra["devices"] == 1,
              f"{method}: device counts")
        check(four.extra["client_shards"] == 4,
              f"{method}: client axis spans {four.extra['client_shards']} "
              "devices, not 4")
        check(four.extra["use_pallas"] is True, f"{method}: use_pallas")
        check(all(math.isfinite(x) for x in four.eval_loss),
              f"{method}: eval loss {four.eval_loss}")
        ledger = compare_ledgers(method, cfg, four, one, "devices=4",
                                 "devices=1")
        dloss = max(abs(a - b) for a, b in zip(four.eval_loss, one.eval_loss))
        check(dloss <= LOSS_TOL, f"{method}: eval loss devices=4 "
              f"{four.eval_loss} vs devices=1 {one.eval_loss}")
        print(f"sharded {method}: devices=4 client_shards="
              f"{four.extra['client_shards']} ledger={ledger} uplink_bytes="
              f"{four.ledger.uplink_total:.0f} (devices=1: "
              f"{one.ledger.uplink_total:.0f}) eval_loss={four.eval_loss[-1]:.6f}"
              f" (devices=1: {one.eval_loss[-1]:.6f}) max_dloss={dloss:.3g} "
              f"({time.perf_counter() - t0:.1f} s)", flush=True)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-engine phase")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.compile_cache import (CompileWatcher,
                                                enable_compilation_cache)
    except ImportError as e:
        fail(f"the repository's src/ is not next to this script ({e})")

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"JAX finds no TPU (platform {dev.platform!r})")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} but JAX finds {len(devices)} devices")
    cache = enable_compilation_cache()
    watcher = CompileWatcher.install()
    print(f"device: {dev.device_kind} x{len(devices)}, compile cache {cache}",
          flush=True)

    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase()
    else:
        kernel_phase()
        for label, method, kw in METHODS:
            run_method(label, method, kw, watcher)
    print(f"total {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
