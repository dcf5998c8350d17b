"""K-round scan-fused client-parallel FL round engine (DESIGN.md Secs. 8-11).

One jitted XLA program covers a **chunk of K rounds** (``FLConfig.
scan_rounds``), for **every** uplink method:

  * the chunk body is a ``lax.scan`` whose step is one complete FL round:
    in-jit client selection from a folded key chain
    (``simulation.select_round_clients``), vmapped local training, the
    method-generic codec encode (``vmap(codec.encode)`` over clients),
    reconstruction, client averaging, the optional in-jit downlink codec,
    and the server parameter update;
  * the round body is **branch-free across rounds**: there are no
    jit-static per-round arguments left.  GradESTC's Formula-13 candidate
    count ``d`` is traced shared state masking rank-padded buffers
    (``core/gradestc.compress_step``), and init / steady / mixed
    partial-participation rounds all take the same code path -- so the
    scan's single trace serves every round and nothing recompiles mid-run;
  * the scan stacks each round's packed int32 stats vector into a
    ``(K, stats_len)`` block, and exactly **one** device->host transfer
    leaves the program per chunk: that block, which
    :class:`repro.fl.compression.RoundAccountant` -- shared verbatim with
    the reference loop -- turns row by row into exact integer-bit ledger
    charges.

The host loop therefore dispatches once per chunk and syncs once per K
rounds.  Chunks never span an eval round (``plan_chunks``), so parameters
materialize exactly at eval points and trajectories / ledger bytes are
invariant in K; a run compiles one executable per distinct chunk length
(typically {1, K, remainder} -- measured via ``FLResult.extra
["chunk_compiles"]``).  Chunk c+1's batches are drawn before chunk c's
eval sync and its stats fetch is deferred one chunk, so the draws and the
accounting overlap device compute; all chunk inputs are donated (nothing
is ever replayed -- the speculation / spec-miss / donation-suppression
machinery of the old per-round engine is gone, because the statics it
speculated on no longer exist).

Scaling across a device mesh (``FLConfig.devices > 1``): the same chunk
runs under ``shard_map`` on a ``("data", "model")`` mesh
(``launch/mesh.make_fl_mesh``) with the scan *inside* the shard_map body.
The selected-client axis -- local training, per-client wire/stats, the
gathered slice of the stacked codec state -- shards over ``"data"``; model
params, codec shared state, and the persistent per-client state store stay
replicated.  Cross-shard traffic is exactly two collectives per round (one
psum of the concatenated masked reconstruction sums, one all_gather of the
[stats | bitcast state] int32 rows), so the stacked stats block and
the single per-chunk host sync survive sharding unchanged and ledger bytes
are *identical* to the single-device program.  Client counts that do not
divide the mesh are padded in-jit with a mirrored client and masked out.

The per-client Python loop (``simulation._run_fl_loop``) stays as the
parity oracle; ``tests/test_round_engine.py`` and
``tests/test_sharded_engine.py`` pin every engine configuration to it.

Tracing (DESIGN.md "Tracing"): the host loop marks its phases with
``core.metrics.span`` -- ``fl.assemble`` / ``fl.draw`` (the chunk's batch
block), ``fl.dispatch``, ``fl.drain``, ``fl.eval`` -- and the round body
names its device phases ``fl_encode``, ``fl_aggregate`` and ``fl_server``
(``jax.named_scope``; they reach each compiled op's ``op_name``).  Local
training keeps its own name, ``jit(local_train)``.
"""

from __future__ import annotations

import functools
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.codecs import SERVER_CLIENT_ID
from repro.core.metrics import host_fetch, span

from .compression import (
    RoundAccountant,
    build_codecs,
    build_downlink_codecs,
    pack_round_stats,
    round_base_key,
)
from .simulation import (
    FLConfig,
    FLResult,
    _flatten_groups,
    _set_groups,
    _setup_run,
    make_local_train,
    select_round_clients,
)

__all__ = ["run_fl_fused", "plan_chunks", "ENCODE_SCOPE", "AGGREGATE_SCOPE",
           "SERVER_SCOPE"]

#: ``jax.named_scope`` names of the round body's phases after local
#: training, shared by the single-device and sharded programs: the codec
#: encode; the client deltas through the aggregated mean (collectives,
#: ``reduce_stats``, ``update_shared``, ``from_wire``); the server step
#: (server lr, downlink codec, new params, packed stats).
ENCODE_SCOPE = "fl_encode"
AGGREGATE_SCOPE = "fl_aggregate"
SERVER_SCOPE = "fl_server"


# ---------------------------------------------------------------------------
# chunk planning
# ---------------------------------------------------------------------------

def plan_chunks(rounds: int, eval_every: int, scan_rounds: int
                ) -> List[Tuple[int, int]]:
    """Partition ``range(rounds)`` into scan chunks ``[start, end)``.

    A chunk grows until it holds ``scan_rounds`` rounds or its last round
    is an eval round (``r % eval_every == 0 or r == rounds - 1``), whichever
    comes first -- so parameters always materialize exactly at eval points
    and the eval cadence is invariant in K.  The resulting chunk lengths
    take at most three distinct values ({1, K, remainder} in the common
    case), each of which compiles exactly once.
    """
    scan_rounds = max(1, int(scan_rounds))
    chunks: List[Tuple[int, int]] = []
    start = 0
    while start < rounds:
        end = start
        for r in range(start, min(start + scan_rounds, rounds)):
            end = r + 1
            if r % eval_every == 0 or r == rounds - 1:
                break
        chunks.append((start, end))
        start = end
    return chunks


# ---------------------------------------------------------------------------
# chunk program builders
# ---------------------------------------------------------------------------

def _apply_downlink(dl_codecs, dl_state, dl_shared, avg, base_key):
    """Optional downlink codec: the server compresses the aggregated update
    once; every client mirrors the shared decompressor, so the server
    applies the *reconstruction* to stay bit-identical with clients -- all
    in-jit, its stats ride the same packed transfer.  ``avg`` is mutated in
    place.  Shared by the single-device and sharded programs (under
    ``shard_map`` it runs replicated: every shard computes the identical
    server-side encode from the psum'd mean)."""
    new_dl_state, new_dl_shared = dict(dl_state), dict(dl_shared)
    dl_reds: Dict[str, jnp.ndarray] = {}
    for path, dlc in dl_codecs.items():
        wire = dlc.to_wire(avg[path])
        cst2, recon_w, stats = dlc.encode(dl_state[path], dl_shared[path],
                                          base_key, wire)
        new_dl_state[path] = cst2
        red = dlc.reduce_stats(stats[None])
        new_dl_shared[path] = dlc.update_shared(dl_shared[path], red, recon_w)
        avg[path] = dlc.from_wire(
            recon_w, avg[path].shape).astype(avg[path].dtype)
        dl_reds[path] = red
    return new_dl_state, new_dl_shared, dl_reds


def _server_step(server_lr, dl_codecs, params, flat_g, recon_mean, reds,
                 new_cstate, new_shared, dl_state, dl_shared, base_key):
    """The round's tail after aggregation, shared by both programs: scale
    the mean update by the server lr, run the optional downlink codec,
    apply the update and pack the round's stats.  Returns the scan step's
    ``(carry, packed)``."""
    avg = {p: recon_mean[p] * server_lr for p in flat_g}
    new_dl_state, new_dl_shared, dl_reds = _apply_downlink(
        dl_codecs, dl_state, dl_shared, avg, base_key)
    new_flat = {p: flat_g[p] + avg[p].astype(flat_g[p].dtype) for p in flat_g}
    new_params = _set_groups(params, new_flat)
    packed = pack_round_stats(reds, dl_reds)
    return (new_params, new_cstate, new_shared, new_dl_state,
            new_dl_shared), packed


def _build_chunk(arch, lr: float, server_lr: float, codecs, dl_codecs,
                 group_paths, seed: int, n_clients: int, n_sel: int):
    """Returns the jitted single-device ``chunk_fn``: a ``lax.scan`` of the
    branch-free round body over the chunk's stacked batch blocks.  All
    carried state (params, codec client/shared state, downlink state) is
    donated -- nothing is ever redispatched."""
    local_train = make_local_train(arch, lr)
    full_part = (n_sel == n_clients)

    def round_body(carry, xs):
        params, cstate, shared, dl_state, dl_shared = carry
        batches, rnd = xs                      # batches: {k: (C_sel, ...)}
        sel = select_round_clients(seed, rnd, n_clients, n_sel)
        base_key = round_base_key(seed, rnd)

        def take(x):
            return x if full_part else x[sel]

        def put(x, upd):
            return upd if full_part else x.at[sel].set(upd)

        locals_ = jax.vmap(local_train, in_axes=(None, 0))(params, batches)
        flat_g = _flatten_groups(params, group_paths)
        flat_l = _flatten_groups(locals_, group_paths)

        new_cstate, new_shared = dict(cstate), dict(shared)
        recon_mean: Dict[str, jnp.ndarray] = {}
        reds: Dict[str, jnp.ndarray] = {}
        for path in group_paths:
            codec = codecs.get(path)
            with jax.named_scope(AGGREGATE_SCOPE):
                delta = flat_l[path] - flat_g[path][None]      # (C_sel, ...)
                if codec is None:
                    recon_mean[path] = jnp.sum(delta, 0) / delta.shape[0]
                    continue
            with jax.named_scope(ENCODE_SCOPE):
                wire = jax.vmap(codec.to_wire)(delta)
                ckeys = jax.vmap(
                    lambda c, _co=codec: _co.per_client_key(base_key, c)
                )(sel)
                cst = jax.tree.map(take, cstate[path])
                cst2, recon, stats = jax.vmap(
                    codec.encode, in_axes=(0, None, 0, 0)
                )(cst, shared[path], ckeys, wire)
                new_cstate[path] = jax.tree.map(put, cstate[path], cst2)
            with jax.named_scope(AGGREGATE_SCOPE):
                red = codec.reduce_stats(stats)
                mean_wire = jnp.sum(recon, 0) / delta.shape[0]
                new_shared[path] = codec.update_shared(shared[path], red,
                                                       mean_wire)
                recon_mean[path] = codec.from_wire(
                    mean_wire, flat_g[path].shape).astype(delta.dtype)
            reds[path] = red

        with jax.named_scope(SERVER_SCOPE):
            return _server_step(server_lr, dl_codecs, params, flat_g,
                                recon_mean, reds, new_cstate, new_shared,
                                dl_state, dl_shared, base_key)

    # Only the carried state is donated: the int32 batch block has no
    # same-shape output to alias with, so donating it just trips XLA's
    # unusable-donation warning every chunk.
    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
    def chunk_fn(params, cstate, shared, dl_state, dl_shared, batches,
                 round_ids):
        carry, packed = jax.lax.scan(
            round_body, (params, cstate, shared, dl_state, dl_shared),
            (batches, round_ids))
        return carry + (packed,)

    return chunk_fn


def _as_i32(leaf: jnp.ndarray) -> jnp.ndarray:
    """Lossless (C_loc, -1) int32 view of a codec-state leaf, so every
    per-client state update rides *one* fused all-gather regardless of
    dtype mix (f32 bases, uint32 key stacks, bool init flags, int32 d)."""
    if leaf.dtype == jnp.bool_:
        flat = leaf.astype(jnp.int32)
    else:
        assert leaf.dtype.itemsize == 4, leaf.dtype
        flat = jax.lax.bitcast_convert_type(leaf, jnp.int32)
    return flat.reshape(flat.shape[0], -1)


def _from_i32(col: jnp.ndarray, dtype, shape) -> jnp.ndarray:
    if jnp.dtype(dtype) == jnp.bool_:
        return (col != 0).reshape(shape)
    return jax.lax.bitcast_convert_type(
        col.reshape(shape).astype(jnp.int32), jnp.dtype(dtype))


def _build_sharded_chunk(arch, lr: float, server_lr: float, codecs,
                         dl_codecs, group_paths, rspecs, seed: int,
                         n_clients: int, n_sel: int, c_pad: int):
    """The same chunk as ``_build_chunk``, under ``shard_map`` -- the scan
    runs *inside* the shard_map body, so per-round cross-shard traffic is
    still exactly **two collectives** (on an oversubscribed CPU mesh every
    collective is a lockstep barrier, so per-group/per-leaf collectives
    dominated the round until they were fused):

      * one ``psum`` of the concatenated mask-weighted reconstruction sums
        (compressed groups' recon wire + raw groups' dense deltas, all f32);
      * one ``all_gather`` of the concatenated per-client int32 row
        [per-group stats | bitcast codec-state update] (row order is the
        padded selection order, which every shard holds replicated), sliced
        back to the real (unpadded) clients so ``reduce_stats`` sees
        *exactly* the rows the single-device program reduces -- packed
        stats, and therefore ledger bytes, are identical by construction.
        The gathered state columns scatter into the replicated store
        (padding lanes mirror client ``sel[0]`` and scatter its identical
        update, so duplicates are benign).

    Each shard derives the round's full selection in-jit from the folded
    key chain (replicated arithmetic), pads it to ``c_pad`` with a mirror
    of ``sel[0]``, and slices its local lane block -- matching the padded
    host batch layout by construction.  Everything after the collectives
    (shared-state update incl. in-jit Formula 13, downlink codec, server
    step) is computed redundantly-replicated on every shard, keeping all
    scan carries ``P()``.
    """
    local_train = make_local_train(arch, lr)
    mesh = rspecs.mesh
    ax = rspecs.client_axis_name
    n_shards = rspecs.n_shards
    c_loc = c_pad // n_shards

    def shard_index():
        if isinstance(ax, tuple):
            i = jnp.zeros((), jnp.int32)
            for a in ax:
                i = i * jax.lax.psum(1, a) + jax.lax.axis_index(a)
            return i
        return jax.lax.axis_index(ax)

    def round_body(carry, xs):
        params, cstate, shared, dl_state, dl_shared = carry
        batches, rnd = xs                     # batches: {k: (C_loc, ...)}
        base_key = round_base_key(seed, rnd)
        sel_full = select_round_clients(seed, rnd, n_clients, n_sel)
        if c_pad > n_sel:
            sel_full = jnp.concatenate(
                [sel_full,
                 jnp.broadcast_to(sel_full[0], (c_pad - n_sel,))])
        mask_full = (jnp.arange(c_pad) < n_sel).astype(jnp.float32)
        off0 = shard_index() * c_loc
        sel = jax.lax.dynamic_slice(sel_full, (off0,), (c_loc,))
        mask = jax.lax.dynamic_slice(mask_full, (off0,), (c_loc,))

        def cmask(x):          # (C_loc,) mask broadcast against x's rank
            return mask.reshape(mask.shape + (1,) * (x.ndim - 1))

        locals_ = jax.vmap(local_train, in_axes=(None, 0))(params, batches)
        flat_g = _flatten_groups(params, group_paths)
        flat_l = _flatten_groups(locals_, group_paths)

        # ---- per-shard phase: encode local clients, stage collective rows
        sums = {}                       # path -> local masked sum (wire/raw)
        int_cols = []
        state_cols: Dict[str, list] = {}
        state_meta: Dict[str, tuple] = {}
        stats_of: Dict[str, jnp.ndarray] = {}
        for path in group_paths:
            codec = codecs.get(path)
            with jax.named_scope(AGGREGATE_SCOPE):
                delta = flat_l[path] - flat_g[path][None]      # (C_loc, ...)
                if codec is None:
                    sums[path] = jnp.sum(delta * cmask(delta), 0)
                    continue
            with jax.named_scope(ENCODE_SCOPE):
                wire = jax.vmap(codec.to_wire)(delta)
                ckeys = jax.vmap(
                    lambda c, _co=codec: _co.per_client_key(base_key, c)
                )(sel)
                cst = jax.tree.map(lambda x: x[sel], cstate[path])
                cst2, recon, stats = jax.vmap(
                    codec.encode, in_axes=(0, None, 0, 0)
                )(cst, shared[path], ckeys, wire)
            with jax.named_scope(AGGREGATE_SCOPE):
                sums[path] = jnp.sum(recon * cmask(recon), 0)
                int_cols.append(stats)
                leaves, treedef = jax.tree.flatten(cst2)
                state_cols[path] = [_as_i32(lf) for lf in leaves]
                state_meta[path] = (treedef, [lf.shape for lf in leaves],
                                    [lf.dtype for lf in leaves])

        # ---- the collectives and the replicated phase: aggregation ----
        with jax.named_scope(AGGREGATE_SCOPE):
            # ---- collective 1: fused psum of every group's masked sum ----
            flat_sums = jnp.concatenate(
                [sums[p].reshape(-1).astype(jnp.float32)
                 for p in group_paths])
            flat_sums = jax.lax.psum(flat_sums, ax)
            mean_of: Dict[str, jnp.ndarray] = {}
            off = 0
            for path in group_paths:
                size = int(np.prod(sums[path].shape))
                mean_of[path] = (flat_sums[off: off + size]
                                 .reshape(sums[path].shape) / n_sel)
                off += size

            # ---- collective 2: fused all-gather of [stats | state] rows --
            # (row i belongs to padded-selection lane i == client sel_full[i],
            # which every shard already holds replicated -- no id column
            # travels.  Raw-only methods have no rows at all and skip the
            # collective entirely.)
            for path in state_cols:
                int_cols.extend(state_cols[path])
            if int_cols:
                gathered = jax.lax.all_gather(
                    jnp.concatenate(int_cols, axis=1), ax, axis=0, tiled=True)
            else:
                gathered = jnp.zeros((c_pad, 0), jnp.int32)
            sel_all = sel_full
            off = 0
            for path in group_paths:
                codec = codecs.get(path)
                if codec is None:
                    continue
                stats_of[path] = gathered[:n_sel,
                                          off: off + codec.client_stats_len]
                off += codec.client_stats_len
            new_cstate = dict(cstate)
            for path, (treedef, shapes, dtypes) in state_meta.items():
                upd = []
                for shape, dtype in zip(shapes, dtypes):
                    size = int(np.prod(shape[1:], dtype=np.int64))
                    col = gathered[:, off: off + size]
                    upd.append(_from_i32(col, dtype,
                                         (gathered.shape[0],) + shape[1:]))
                    off += size
                new_cstate[path] = jax.tree.map(
                    lambda x, u: x.at[sel_all].set(u),
                    cstate[path], jax.tree.unflatten(treedef, upd))

            # ---- replicated phase: identical on every shard --------------
            new_shared = dict(shared)
            recon_mean: Dict[str, jnp.ndarray] = {}
            reds: Dict[str, jnp.ndarray] = {}
            for path in group_paths:
                codec = codecs.get(path)
                if codec is None:
                    recon_mean[path] = mean_of[path]
                    continue
                red = codec.reduce_stats(stats_of[path])
                new_shared[path] = codec.update_shared(shared[path], red,
                                                       mean_of[path])
                recon_mean[path] = codec.from_wire(
                    mean_of[path], flat_g[path].shape
                ).astype(flat_g[path].dtype)
                reds[path] = red

        with jax.named_scope(SERVER_SCOPE):
            return _server_step(server_lr, dl_codecs, params, flat_g,
                                recon_mean, reds, new_cstate, new_shared,
                                dl_state, dl_shared, base_key)

    def core(params, cstate, shared, dl_state, dl_shared, batches,
             round_ids):
        carry, packed = jax.lax.scan(
            round_body, (params, cstate, shared, dl_state, dl_shared),
            (batches, round_ids))
        return carry + (packed,)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
    def chunk_fn(params, cstate, shared, dl_state, dl_shared, batches,
                 round_ids):
        smapped = jax.shard_map(
            core, mesh=mesh,
            in_specs=(P(), P(), P(), P(), P(),
                      rspecs.batch_chunk(batches), P()),
            out_specs=(P(), P(), P(), P(), P(), P()),
            check_vma=False,
        )
        return smapped(params, cstate, shared, dl_state, dl_shared, batches,
                       round_ids)

    return chunk_fn


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run_fl_fused(cfg: FLConfig,
                 progress: Optional[Callable[[int, dict], None]] = None) -> FLResult:
    t0 = time.time()
    su = _setup_run(cfg)
    arch, params, policy = su.arch, su.params, su.policy
    eval_fn, eval_block = su.eval_fn, su.eval_block
    ledger, group_paths, n_sel = su.ledger, su.group_paths, su.n_sel

    use_pallas = (jax.default_backend() == "tpu"
                  if cfg.use_pallas is None else cfg.use_pallas)
    C = cfg.n_clients
    ndev = int(cfg.devices or 1)
    K = max(1, int(cfg.scan_rounds))

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

    c_pad = n_sel
    if ndev > 1:
        from repro.launch.mesh import make_fl_mesh
        from repro.launch.sharding import FLRoundSpecs, make_plan

        mesh = make_fl_mesh(ndev)
        rspecs = FLRoundSpecs(make_plan(mesh, arch))
        c_pad = rspecs.pad_clients(n_sel)
        # Commit everything replicated up front so donated buffers alias
        # across chunks instead of being re-laid-out on first use.
        params = rspecs.put_replicated(params)
        cstate = rspecs.put_replicated(cstate)
        shared = rspecs.put_replicated(shared)
        dl_state = rspecs.put_replicated(dl_state)
        dl_shared = rspecs.put_replicated(dl_shared)
        chunk_fn = _build_sharded_chunk(arch, cfg.lr, cfg.server_lr, codecs,
                                        dl_codecs, group_paths, rspecs,
                                        cfg.seed, C, n_sel, c_pad)

        def place(block):
            return rspecs.put_batch_chunk(block)
    else:
        chunk_fn = _build_chunk(arch, cfg.lr, cfg.server_lr, codecs,
                                dl_codecs, group_paths, cfg.seed, C, n_sel)

        def place(block):
            return {k: jnp.asarray(v) for k, v in block.items()}

    # The whole run's selections in one device computation: a pure function
    # of (seed, round) -- the scan body re-derives the identical chain
    # in-jit, the host only needs it to assemble matching batch blocks.
    sel_table = np.asarray(jax.vmap(
        lambda r: select_round_clients(cfg.seed, r, C, n_sel)
    )(jnp.arange(cfg.rounds)))

    def assemble(start: int, end: int, in_flight: int):
        """Host side of a chunk: the stacked (Kc, C_pad, steps, B, S) batch
        block, drawn per round / per selected client in the same order as
        the reference loop (padding lanes replicate the round's first
        selected client -- the in-jit mirror of ``sel[0]``), then placed.

        Fills one preallocated block per key instead of stacking
        K*C_sel*steps small arrays (the stream side of the draw is in
        ``data/synthetic.py``).  The loop calls it for chunk c+1 while
        chunk c computes, so the draws hide behind the device as long as
        they take less than a chunk.  ``in_flight``: dispatched chunks not
        yet synced, 0 only for the first chunk.  Span ``fl.assemble``
        (placement included), one ``fl.draw`` per batch."""
        kc = end - start
        block: Dict[str, np.ndarray] = {}
        with span("fl.assemble", chunk=start, rounds=kc,
                  batches=kc * n_sel * cfg.local_steps, in_flight=in_flight):
            for i, r in enumerate(range(start, end)):
                for j, c in enumerate(sel_table[r]):
                    stream = su.streams[int(c)]
                    for s in range(cfg.local_steps):
                        with span("fl.draw", chunk=start, client=int(c)):
                            b = next(stream)
                        if not block:
                            block = {
                                kk: np.empty(
                                    (kc, c_pad, cfg.local_steps)
                                    + np.shape(v), np.asarray(v).dtype)
                                for kk, v in b.items()}
                        for kk, v in b.items():
                            block[kk][i, j, s] = v
            if c_pad > n_sel:
                for v in block.values():
                    v[:, n_sel:] = v[:, :1]
            return place(block)

    chunks = plan_chunks(cfg.rounds, cfg.eval_every, K)
    res = FLResult([], [], [], [], ledger, 0.0)
    pending = None          # (stacked packed stats device array, start, end)

    def drain():
        nonlocal pending
        if pending is not None:
            with span("fl.drain", chunk=pending[1]):
                rows = host_fetch(pending[0])          # one fetch per chunk
                for i, r in enumerate(range(pending[1], pending[2])):
                    acct.consume(rows[i], ledger, r)
            pending = None

    client_shards = 0       # shards of the placed batch block's client axis
    batches = assemble(*chunks[0], in_flight=0) if chunks else None
    for (start, end), nxt in zip(chunks, chunks[1:] + [None]):
        for _ in range(start, end):
            ledger.begin_round()
        if not client_shards:
            blk = next(iter(batches.values()))
            client_shards = c_pad // blk.sharding.shard_shape(blk.shape)[1]
        # host numpy, not jnp.arange: an eager jnp.arange bakes (start, end)
        # as constants and would compile a fresh tiny executable per chunk.
        round_ids = np.arange(start, end, dtype=np.int32)
        with span("fl.dispatch", chunk=start, rounds=end - start) as sp:
            built = chunk_fn._cache_size()
            out = chunk_fn(params, cstate, shared, dl_state, dl_shared,
                           batches, round_ids)
            sp.set_metadata(new_programs=chunk_fn._cache_size() - built)
        params, cstate, shared, dl_state, dl_shared, packed = out
        # Before this iteration's host syncs (eval included): the next
        # chunk's draws, then the previous chunk's stats, overlap this one.
        if nxt is not None:
            batches = assemble(*nxt, in_flight=1 + (pending is not None))
        drain()
        pending = (packed, start, end)
        if hasattr(packed, "copy_to_host_async"):
            packed.copy_to_host_async()
        rnd = end - 1
        if rnd % cfg.eval_every == 0 or rnd == cfg.rounds - 1:
            drain()                       # ledger exact before reporting
            with span("fl.eval", round=rnd):
                la = host_fetch(eval_fn(params, eval_block))
            res.eval_rounds.append(rnd)
            res.eval_loss.append(float(la[0]))
            res.eval_acc.append(float(la[1]))
            res.uplink_bytes.append(ledger.uplink_total)
            if progress:
                progress(rnd, {"loss": res.eval_loss[-1],
                               "acc": res.eval_acc[-1],
                               "uplink": ledger.uplink_total})
    drain()

    res.wall_s = time.time() - t0
    res.extra["engine"] = "fused"
    res.extra["use_pallas"] = use_pallas
    res.extra["uplink_stats"] = [acct.uplink_stats[r]
                                 for r in sorted(acct.uplink_stats)]
    res.extra["devices"] = ndev
    res.extra["scan_rounds"] = K
    res.extra["chunks"] = len(chunks)
    res.extra["chunk_shapes"] = len({e - s for s, e in chunks})
    # One executable per distinct chunk length == zero mid-run recompiles;
    # asserted by tests and the CI recompile guard.
    res.extra["chunk_compiles"] = int(chunk_fn._cache_size())
    res.extra["client_shards"] = client_shards
    res.extra.update(acct.metrics)
    return res
