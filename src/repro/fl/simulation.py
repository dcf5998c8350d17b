"""Federated-learning round loop (benchmark-scale, single host).

Implements the paper's experimental protocol (Sec. V): N clients, all (or a
sampled fraction) participating per round, each performing ``local_steps``
SGD steps before uploading its model delta through the configured uplink
compression method; the server averages reconstructed deltas and applies
them with a server learning rate (1.0 = FedAvg).

Two round engines share this entry point (DESIGN.md Sec. 8), and both are
generic over the stateless codec protocol (``repro.core.codecs``), so every
method -- GradESTC, the six Table III baselines, and the optional downlink
codec -- runs on either engine:

* ``engine="fused"`` (default) -- the K-round scan-fused engine in
  ``repro/fl/engine.py``: one jitted XLA program per chunk of
  ``scan_rounds`` rounds (a ``lax.scan`` over the branch-free round body),
  local training vmapped over clients, stacked codec state, in-jit
  client selection / aggregation / Formula-13 / downlink compression, one
  packed-stats host sync per chunk.
* ``engine="loop"``  -- the per-client Python reference loop below, kept as
  the parity oracle (identical math, one dispatch per client per group, but
  the same single packed-stats ``host_fetch`` per round -- byte accounting
  shares ``RoundAccountant`` with the fused engine, so it is exact-integer
  on both).

Client selection is a pure function of ``(seed, round)``
(:func:`select_round_clients` -- a ``fold_in`` key chain), so the scan
body derives it in-jit while the host assembles the matching batch blocks
from the same chain; there is no hidden host RNG state.

The distributed SPMD path (pjit over the production mesh) lives in
``repro/launch`` -- this module is the algorithm-fidelity / communication-
accounting harness used by tests, benchmarks, and the examples.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.codecs import SERVER_CLIENT_ID
from repro.core.metrics import CommLedger, host_fetch, span
from repro.core.policy import make_policy
from repro.data import client_batch_stream, make_task
from repro.models import loss_fn, model, param_group_shapes
from repro.models.config import ArchConfig
from repro.optim import sgd

from .compression import (
    RoundAccountant,
    build_codecs,
    build_downlink_codecs,
    make_method,
    pack_round_stats,
    round_base_key,
)

__all__ = ["FLConfig", "FLResult", "run_fl", "default_tiny_arch",
           "make_local_train", "make_eval_step", "make_batched_eval",
           "select_round_clients"]


def select_round_clients(seed: int, rnd, n_clients: int, n_sel: int):
    """The round's selected client ids, sorted -- a pure function of
    ``(seed, round)`` via a ``fold_in`` chain.

    ``rnd`` may be a traced int32, so the scan-fused engine derives the
    selection *inside* the jitted chunk, while the host (batch assembly,
    reference loop) evaluates the identical chain concretely -- both sides
    agree by construction, with no ``np.random.Generator`` state to keep in
    sync."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed + 0xC11E47), rnd)
    perm = jax.random.permutation(key, n_clients)
    return jnp.sort(perm[:n_sel]).astype(jnp.int32)


def default_tiny_arch(vocab: int = 256) -> ArchConfig:
    """Small-but-real transformer for CPU-scale FL experiments (~1.6M params,
    the LeNet5-of-this-codebase)."""
    return ArchConfig(
        name="fl-tiny", family="dense", n_layers=4, d_model=128, n_heads=4,
        n_kv_heads=4, d_ff=512, vocab=vocab, dtype="float32", remat=False,
        attn_chunk=0,
    )


@dataclass
class FLConfig:
    method: str = "gradestc"
    rounds: int = 30
    n_clients: int = 10
    participation: float = 1.0       # fraction of clients per round
    local_steps: int = 4
    batch: int = 16
    seq: int = 64
    lr: float = 0.05
    server_lr: float = 1.0
    alpha: Optional[float] = None    # None = IID; 0.5 / 0.1 = paper's non-IID
    #: compress the server->client broadcast through a shared server-side
    #: GradESTC codec (the paper's Sec. VI future work; beyond-paper).
    downlink_compress: bool = False
    seed: int = 0
    eval_every: int = 5
    eval_batches: int = 4
    arch: Optional[ArchConfig] = None
    method_kw: Dict[str, Any] = field(default_factory=dict)
    policy_overrides: Dict[str, tuple] = field(default_factory=dict)
    coverage_target: float = 0.90
    min_params: int = 4096           # tiny model -> lower floor than prod
    #: "fused" = K-round scan chunk engine (engine.py); "loop" = per-client
    #: reference loop (the parity oracle).  Every method, including
    #: downlink compression, runs on either engine.
    engine: str = "fused"
    #: chunk length K of the fused engine: one jitted dispatch and one
    #: packed-stats host sync cover K rounds (``lax.scan`` inside the
    #: chunk program).  Chunks never span an eval round, so trajectories
    #: and the ledger are invariant in K; 1 recovers the per-round fused
    #: engine.  Shapes depend only on the chunk length, so a run compiles
    #: once per distinct length (typically {1, K, remainder}).
    scan_rounds: int = 8
    #: route the compression hot paths through the Pallas kernels -- the
    #: GradESTC A/E projection + reconstruction and the FedPAQ/FedQClip
    #: block quantizer.  None = auto (True on TPU, False elsewhere).
    use_pallas: Optional[bool] = None
    #: data-parallel device count for the fused engine: the selected-client
    #: axis of one round shards over a ("data", "model") mesh
    #: (``launch/mesh.make_fl_mesh``) under ``shard_map``.  None/1 = the
    #: single-device program.  Ledger bytes are identical either way.
    devices: Optional[int] = None


@dataclass
class FLResult:
    eval_rounds: List[int]
    eval_loss: List[float]
    eval_acc: List[float]
    uplink_bytes: List[float]        # cumulative at each eval point
    ledger: CommLedger
    wall_s: float
    extra: Dict[str, Any] = field(default_factory=dict)

    def uplink_at_loss(self, target: float) -> Optional[float]:
        """Cumulative uplink bytes when eval loss first reaches target."""
        for r, l, b in zip(self.eval_rounds, self.eval_loss, self.uplink_bytes):
            if l <= target:
                return b
        return None

    def uplink_at_acc(self, target: float) -> Optional[float]:
        for r, a, b in zip(self.eval_rounds, self.eval_acc, self.uplink_bytes):
            if a >= target:
                return b
        return None


def _flatten_groups(params, groups) -> Dict[str, jnp.ndarray]:
    """{group_path: array} view of the param pytree."""
    out = {}
    for path in groups:
        node = params
        for part in path.split("/"):
            node = node[part]
        out[path] = node
    return out


def _set_groups(params, updates: Dict[str, jnp.ndarray]):
    new = jax.tree.map(lambda x: x, params)   # shallow-copy containers

    def setpath(tree, parts, val):
        if len(parts) == 1:
            tree = dict(tree)
            tree[parts[0]] = val
            return tree
        tree = dict(tree)
        tree[parts[0]] = setpath(tree[parts[0]], parts[1:], val)
        return tree

    for path, val in updates.items():
        new = setpath(new, path.split("/"), val)
    return new


def make_local_train(arch: ArchConfig, lr: float):
    """Jitted ``local_steps`` SGD scan; batches: {k: (steps, B, S)}.

    Shared by both engines -- the fused engine vmaps this exact function over
    the selected-client axis, so per-client math is identical to the loop.
    """
    opt_init, opt_update = sgd(lr)

    @jax.jit
    def local_train(p, batches):
        st = opt_init(p)

        def step(carry, b):
            p, st = carry
            g = jax.grad(lambda pp: loss_fn(arch, pp, b))(p)
            p, st = opt_update(g, st, p)
            return (p, st), None

        (p2, _), _ = jax.lax.scan(step, (p, st), batches)
        return p2

    return local_train


def make_eval_step(arch: ArchConfig):
    @jax.jit
    def eval_step(p, batch):
        logits = model.forward(arch, p, batch)
        labels = batch["labels"]
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        acc = jnp.mean(jnp.argmax(logits, -1) == labels)
        return jnp.mean(logz - gold), acc

    return eval_step


def make_batched_eval(arch: ArchConfig):
    """One jitted eval over the *stacked* eval block {k: (E, B, S)}.

    Returns a length-2 f32 vector [mean loss, mean acc] so an eval round
    costs exactly one device->host fetch (via ``core.metrics.host_fetch``),
    not one blocking ``float()`` per batch -- the per-batch Python loop was
    the last host-sync storm left in the round engines."""
    eval_step = make_eval_step(arch)

    @jax.jit
    def eval_all(p, batch_block):
        # lax.map, not vmap: one batch of activations live at a time, so
        # raising eval_batches does not multiply peak eval memory.
        ls, accs = jax.lax.map(lambda b: eval_step(p, b), batch_block)
        return jnp.stack([jnp.mean(ls), jnp.mean(accs)]).astype(jnp.float32)

    return eval_all


@dataclass
class _RunSetup:
    """Everything both engines must construct *identically* for parity:
    model/task/policy, per-client data streams, eval batches, and the
    participation count.  Built in exactly one place.  (Client selection is
    not here: it is the stateless :func:`select_round_clients` chain.)"""

    arch: ArchConfig
    task: Any
    params: Any
    groups: Dict[str, tuple]
    group_paths: List[str]
    policy: Any
    method: Any
    streams: Dict[int, Any]
    eval_block: Dict[str, jnp.ndarray]
    eval_fn: Callable
    ledger: CommLedger
    n_sel: int


def _setup_run(cfg: FLConfig) -> _RunSetup:
    with span("fl.setup", clients=cfg.n_clients):
        arch = cfg.arch or default_tiny_arch()
        task = make_task(vocab=arch.vocab, n_clients=cfg.n_clients,
                         alpha=cfg.alpha, seed=cfg.seed)
        params = model.init_params(arch, jax.random.PRNGKey(cfg.seed))
        groups = param_group_shapes(arch)
        policy = make_policy(groups, overrides=cfg.policy_overrides,
                             coverage_target=cfg.coverage_target,
                             min_params=cfg.min_params)
        method = make_method(cfg.method, policy=policy, seed=cfg.seed,
                             **cfg.method_kw)
        streams = {c: client_batch_stream(task, c, cfg.batch, cfg.seq,
                                          cfg.seed)
                   for c in range(cfg.n_clients)}
        eval_stream = client_batch_stream(task, -1, cfg.batch, cfg.seq,
                                          cfg.seed + 999)
        eval_batches = [next(eval_stream) for _ in range(cfg.eval_batches)]
        eval_block = {k: jnp.stack([b[k] for b in eval_batches])
                      for k in eval_batches[0]}
        return _RunSetup(
            arch=arch, task=task, params=params, groups=groups,
            group_paths=list(groups.keys()), policy=policy, method=method,
            streams=streams, eval_block=eval_block,
            eval_fn=make_batched_eval(arch), ledger=CommLedger(),
            n_sel=max(1, int(round(cfg.participation * cfg.n_clients))),
        )


def run_fl(cfg: FLConfig, progress: Optional[Callable[[int, dict], None]] = None) -> FLResult:
    if cfg.engine not in ("fused", "loop"):
        raise ValueError(f"unknown engine {cfg.engine!r} (want 'fused' or 'loop')")
    if cfg.engine == "fused":
        from .engine import run_fl_fused

        return run_fl_fused(cfg, progress)
    return _run_fl_loop(cfg, progress)


def _run_fl_loop(cfg: FLConfig, progress: Optional[Callable[[int, dict], None]] = None) -> FLResult:
    t0 = time.time()
    su = _setup_run(cfg)
    params = su.params
    eval_fn, eval_block = su.eval_fn, su.eval_block
    streams, ledger = su.streams, su.ledger
    group_paths, n_sel = su.group_paths, su.n_sel
    policy = su.policy
    C = cfg.n_clients

    use_pallas = (jax.default_backend() == "tpu"
                  if cfg.use_pallas is None else cfg.use_pallas)
    codecs = build_codecs(su.method, policy, group_paths, use_pallas, None)
    dl_codecs = (build_downlink_codecs(policy, group_paths, cfg.seed,
                                       use_pallas, None)
                 if cfg.downlink_compress else {})
    acct = RoundAccountant(codecs, dl_codecs, policy, group_paths, n_sel,
                           downlink_enabled=cfg.downlink_compress)

    cstate = {p: c.init_client_state(C) for p, c in codecs.items()}
    shared = {p: c.init_shared_state() for p, c in codecs.items()}
    dl_state = {
        p: jax.tree.map(lambda x: x[0],
                        c.init_client_state(1, client_ids=[SERVER_CLIENT_ID]))
        for p, c in dl_codecs.items()
    }
    dl_shared = {p: c.init_shared_state() for p, c in dl_codecs.items()}
    # One jitted encode per group: the reference loop keeps per-client
    # dispatch granularity (that is what it measures) but not per-op
    # eager overhead.  No static arguments: encode is branch-free across
    # rounds (round-varying config is traced state).
    enc = {p: jax.jit(c.encode) for p, c in codecs.items()}
    upd_shared = {p: jax.jit(c.update_shared) for p, c in codecs.items()}
    dl_enc = {p: jax.jit(c.encode) for p, c in dl_codecs.items()}
    dl_upd_shared = {p: jax.jit(c.update_shared) for p, c in dl_codecs.items()}

    local_train = make_local_train(su.arch, cfg.lr)

    res = FLResult([], [], [], [], ledger, 0.0)

    for rnd in range(cfg.rounds):
        ledger.begin_round()
        sel = [int(c) for c in
               np.asarray(select_round_clients(cfg.seed, rnd, C, n_sel))]
        base_key = round_base_key(cfg.seed, rnd)

        raw_acc: Dict[str, jnp.ndarray] = {}
        wire_acc: Dict[str, jnp.ndarray] = {}
        stats_rows: Dict[str, list] = {p: [] for p in codecs}
        flat_g = _flatten_groups(params, group_paths)
        for c in sel:
            bs = [next(streams[c]) for _ in range(cfg.local_steps)]
            batches = {k: jnp.stack([b[k] for b in bs]) for k in bs[0]}
            local = local_train(params, batches)
            flat_l = _flatten_groups(local, group_paths)
            for path in group_paths:
                delta = flat_l[path] - flat_g[path]
                codec = codecs.get(path)
                if codec is None:
                    raw_acc[path] = (delta if path not in raw_acc
                                     else raw_acc[path] + delta)
                    continue
                wire = codec.to_wire(delta)
                cst = jax.tree.map(lambda x: x[c], cstate[path])
                ckey = codec.per_client_key(base_key, c)
                cst2, rw, stats = enc[path](cst, shared[path], ckey, wire)
                cstate[path] = jax.tree.map(
                    lambda x, u, _c=c: x.at[_c].set(u), cstate[path], cst2)
                stats_rows[path].append(stats)
                wire_acc[path] = (rw if path not in wire_acc
                                  else wire_acc[path] + rw)

        reds: Dict[str, jnp.ndarray] = {}
        recon_mean: Dict[str, jnp.ndarray] = {}
        for path in group_paths:
            codec = codecs.get(path)
            if codec is None:
                recon_mean[path] = raw_acc[path] / n_sel
                continue
            red = codec.reduce_stats(jnp.stack(stats_rows[path]))
            mean_wire = wire_acc[path] / n_sel
            shared[path] = upd_shared[path](shared[path], red, mean_wire)
            recon_mean[path] = codec.from_wire(
                mean_wire, flat_g[path].shape).astype(flat_g[path].dtype)
            reds[path] = red

        avg = {p: recon_mean[p] * cfg.server_lr for p in group_paths}

        dl_reds: Dict[str, jnp.ndarray] = {}
        for path in group_paths:
            dlc = dl_codecs.get(path)
            if dlc is None:
                continue
            wire = dlc.to_wire(avg[path])
            cst2, rw, stats = dl_enc[path](dl_state[path], dl_shared[path],
                                           base_key, wire)
            dl_state[path] = cst2
            red = dlc.reduce_stats(stats[None])
            dl_shared[path] = dl_upd_shared[path](dl_shared[path], red, rw)
            avg[path] = dlc.from_wire(rw, avg[path].shape).astype(avg[path].dtype)
            dl_reds[path] = red

        params = _set_groups(params, {p: flat_g[p] + avg[p].astype(flat_g[p].dtype)
                                      for p in group_paths})
        jax.block_until_ready(params)

        # ---- the single host sync: same packed layout as the fused engine
        acct.consume(host_fetch(pack_round_stats(reds, dl_reds)), ledger, rnd)

        if rnd % cfg.eval_every == 0 or rnd == cfg.rounds - 1:
            # one jitted eval over the stacked block, one measured fetch --
            # not one blocking float() per batch.
            la = host_fetch(eval_fn(params, eval_block))
            res.eval_rounds.append(rnd)
            res.eval_loss.append(float(la[0]))
            res.eval_acc.append(float(la[1]))
            res.uplink_bytes.append(ledger.uplink_total)
            if progress:
                progress(rnd, {
                    "loss": res.eval_loss[-1], "acc": res.eval_acc[-1],
                    "uplink": ledger.uplink_total,
                })

    res.wall_s = time.time() - t0
    res.extra["engine"] = "loop"
    res.extra["use_pallas"] = use_pallas
    res.extra["uplink_stats"] = [acct.uplink_stats[r]
                                 for r in sorted(acct.uplink_stats)]
    res.extra.update(acct.metrics)
    return res
