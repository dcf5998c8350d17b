"""Fused round engine vs the per-client reference loop (DESIGN.md Sec. 8-9).

The loop path is the parity oracle: same seeds, same data draws, same
fold_in key chains, and -- since both engines share the codec protocol and
``RoundAccountant`` -- the same exact-integer byte accounting.  The fused
engine must reproduce the loop's eval-loss trajectory to float tolerance
and its uplink/downlink byte accounting *exactly*, for every method.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import metrics
from repro.core.codecs import (
    FedPAQCodec, FedQClipCodec, GradESTCCodec, SignSGDCodec, SVDFedCodec,
    TopKCodec, round_base_key,
)
from repro.core.policy import LayerPlan
from repro.core.reshaping import pad_to_block
from repro.fl import FLConfig, run_fl

#: All seven uplink methods of the paper's Table III comparison.  Codecs
#: whose output is a *discrete* function of the input get a looser loss
#: tolerance: batching local training over clients (vmap) schedules the
#: matmul reductions differently than per-client dispatch, so deltas drift
#: by ~1e-7 -- enough to flip a near-tied top-k index or a stochastic-
#: rounding draw, which moves one weight by a whole entry / quantization
#: step.  Byte accounting stays exactly equal in all cases.
METHODS = [
    ("fedavg", 1e-5),
    ("topk", 5e-4),
    ("fedpaq", 5e-4),
    ("signsgd", 1e-5),
    ("fedqclip", 5e-4),
    ("svdfed", 1e-5),
    ("gradestc", 1e-5),
]


def _cfg(**kw):
    base = dict(method="gradestc", rounds=6, n_clients=4, local_steps=1,
                batch=4, seq=16, eval_every=2, seed=1)
    base.update(kw)
    return FLConfig(**base)


def _assert_parity(loop, fused, atol=1e-5):
    assert loop.extra["engine"] == "loop"
    assert fused.extra["engine"] == "fused"
    np.testing.assert_allclose(fused.eval_loss, loop.eval_loss, rtol=0, atol=atol)
    # byte accounting is exact, not approximate
    assert fused.ledger.per_round_uplink == loop.ledger.per_round_uplink
    assert fused.ledger.uplink_total == loop.ledger.uplink_total
    assert fused.ledger.downlink_total == loop.ledger.downlink_total
    assert fused.uplink_bytes == loop.uplink_bytes
    assert fused.extra.get("sum_d") == loop.extra.get("sum_d")


class TestFusedLoopParity:
    @pytest.mark.parametrize("method,atol", METHODS)
    def test_all_methods_trajectory_and_accounting(self, method, atol):
        """Every Table III method runs fused -- no loop fall-back -- and
        matches the reference loop in loss and exact bytes."""
        kw = dict(method=method, rounds=5)
        loop = run_fl(_cfg(engine="loop", **kw))
        fused = run_fl(_cfg(engine="fused", **kw))
        _assert_parity(loop, fused, atol=atol)

    def test_partial_participation_parity(self):
        """Mixed init/update rounds (stragglers initializing late)."""
        kw = dict(participation=0.5, n_clients=6, rounds=5)
        loop = run_fl(_cfg(engine="loop", **kw))
        fused = run_fl(_cfg(engine="fused", **kw))
        _assert_parity(loop, fused)

    def test_partial_participation_stateful_baseline(self):
        kw = dict(method="topk", participation=0.5, n_clients=6, rounds=4)
        loop = run_fl(_cfg(engine="loop", **kw))
        fused = run_fl(_cfg(engine="fused", **kw))
        _assert_parity(loop, fused, atol=5e-4)

    @pytest.mark.parametrize("method", ["gradestc-first", "gradestc-ef",
                                        "gradestc-all", "gradestc-k"])
    def test_variant_parity(self, method):
        kw = dict(method=method, rounds=4, eval_every=3)
        loop = run_fl(_cfg(engine="loop", **kw))
        fused = run_fl(_cfg(engine="fused", **kw))
        _assert_parity(loop, fused)

    @pytest.mark.parametrize("method", ["gradestc", "topk"])
    def test_downlink_codec_parity(self, method):
        """The downlink codec runs in-jit in the fused engine (no loop
        fall-back) and charges exactly what it ships, on both engines."""
        kw = dict(method=method, rounds=4, downlink_compress=True)
        loop = run_fl(_cfg(engine="loop", **kw))
        fused = run_fl(_cfg(engine="fused", **kw))
        _assert_parity(loop, fused, atol=1e-5 if method == "gradestc" else 5e-4)
        raw = run_fl(_cfg(engine="fused", method=method, rounds=4))
        assert fused.ledger.downlink_total < raw.ledger.downlink_total

    @pytest.mark.parametrize("method", ["gradestc", "fedpaq", "topk", "svdfed"])
    def test_single_host_sync_per_chunk(self, method):
        """The scan engine's contract: one device->host fetch per K-round
        chunk, for every method (any codec that silently fell back to
        per-value fetches would fail this).  Eval rounds add exactly one
        measured fetch each -- the stacked-batch eval, not one float() per
        batch.  With K=1 this degrades to exactly one fetch per round."""
        rounds = 6
        metrics.reset_host_sync_count()
        res = run_fl(_cfg(method=method, engine="fused", rounds=rounds,
                          eval_every=100, scan_rounds=4))
        assert res.extra["engine"] == "fused"
        # chunks: (0,1) [round-0 eval], (1,5), (5,6) [final eval]
        assert res.extra["chunks"] == 3
        assert metrics.host_sync_count() == (res.extra["chunks"]
                                             + len(res.eval_rounds))

        metrics.reset_host_sync_count()
        res1 = run_fl(_cfg(method=method, engine="fused", rounds=rounds,
                           eval_every=100, scan_rounds=1))
        assert res1.extra["chunks"] == rounds
        assert metrics.host_sync_count() == rounds + len(res1.eval_rounds)

    def test_loop_obeys_same_sync_budget(self):
        """The reference loop routes byte accounting through the same
        packed-stats path: one measured fetch per round (it used to pay one
        blocking ``float(sc)`` per (client, tensor)), plus one per eval."""
        rounds = 3
        for method in ("gradestc", "topk"):
            metrics.reset_host_sync_count()
            res = run_fl(_cfg(method=method, engine="loop", rounds=rounds,
                              eval_every=100))
            assert res.extra["engine"] == "loop"
            assert metrics.host_sync_count() == rounds + len(res.eval_rounds)

    def test_scan_chunking_invariance(self):
        """The chunk length K is pure dispatch amortization: every K must
        produce the identical trajectory and the identical ledger, byte for
        byte (chunks never span an eval round, so the eval cadence is also
        invariant)."""
        runs = {k: run_fl(_cfg(engine="fused", rounds=7, scan_rounds=k))
                for k in (1, 3, 8)}
        ref = runs[1]
        for k in (3, 8):
            np.testing.assert_allclose(runs[k].eval_loss, ref.eval_loss,
                                       rtol=0, atol=1e-7)
            assert runs[k].eval_rounds == ref.eval_rounds
            assert (runs[k].ledger.per_round_uplink
                    == ref.ledger.per_round_uplink)
            assert runs[k].ledger.uplink_total == ref.ledger.uplink_total
            assert runs[k].extra["chunks"] < ref.extra["chunks"]

    def test_no_mid_run_recompiles(self):
        """The rank-padded traced-d contract, measured two ways: the chunk
        program compiles exactly once per distinct chunk length, and the
        jax.monitoring compile-event stream goes silent once every shape
        has been seen -- Formula 13 moving d between rounds (which used to
        re-bucket a jit-static arg and redispatch) must not trigger a
        single extra XLA compile."""
        from repro.launch.compile_cache import CompileWatcher

        import time

        watcher = CompileWatcher.install()
        mark = watcher.snapshot()
        # chunks: (0,1), (1,5), (5,9) -- the last repeats shape 4, so by
        # its dispatch every shape (and the eval program) is compiled.
        # The eval after round 4 (each eval ends in a host sync) stamps
        # the time from which no compile may follow.
        stamps = {}
        res = run_fl(_cfg(engine="fused", rounds=9, scan_rounds=4,
                          eval_every=4),
                     progress=lambda r, _: stamps.setdefault(
                         r, time.perf_counter()))
        assert res.extra["chunk_shapes"] == 2      # {1, 4}
        assert res.extra["chunk_compiles"] == res.extra["chunk_shapes"]
        assert res.extra["chunks"] == 3
        assert sorted(stamps) == [0, 4, 8]
        n_after, _ = watcher.since(mark, t_start=stamps[4])
        assert n_after == 0, "steady-state chunk triggered an XLA compile"

    def test_pallas_encode_inside_engine_matches(self):
        """use_pallas routes A/E through the kernel (interpret on CPU) and
        must not change the trajectory or the accounting."""
        ref = run_fl(_cfg(engine="fused", rounds=4, use_pallas=False))
        pal = run_fl(_cfg(engine="fused", rounds=4, use_pallas=True))
        assert pal.extra["use_pallas"] is True
        np.testing.assert_allclose(pal.eval_loss, ref.eval_loss, rtol=0, atol=1e-6)
        assert pal.ledger.per_round_uplink == ref.ledger.per_round_uplink

    @pytest.mark.parametrize("method", ["fedpaq", "fedqclip"])
    def test_pallas_block_quantizer_parity(self, method):
        """The quantization codecs take the Pallas block quantizer under the
        same use_pallas flag; engines still agree exactly on bytes (the
        block-local wire format charges one scale per block)."""
        kw = dict(method=method, rounds=3, use_pallas=True)
        loop = run_fl(_cfg(engine="loop", **kw))
        fused = run_fl(_cfg(engine="fused", **kw))
        _assert_parity(loop, fused, atol=5e-4)
        glob = run_fl(_cfg(engine="fused", method=method, rounds=3,
                           use_pallas=False))
        # block-local scales cost more wire than one global scale
        assert fused.ledger.uplink_total > glob.ledger.uplink_total


# ---------------------------------------------------------------------------
# codec protocol properties: shape polymorphism under vmap
# ---------------------------------------------------------------------------

def _codecs_under_test():
    plan = LayerPlan(name="g", shape=(24, 16), stack=2, l=24, m=16, k=4,
                     compress=True)
    n = plan.raw_scalars
    return plan, [
        TopKCodec(n, frac=0.1),
        FedPAQCodec(n, bits=8),
        FedPAQCodec(n, bits=8, use_pallas=True, pallas_interpret=True),
        SignSGDCodec(n),
        FedQClipCodec(n, clip=10.0),
        SVDFedCodec(plan, gamma=8.0, seed=0),
        GradESTCCodec(plan, seed=0, variant="full"),
    ]


class TestCodecProtocol:
    """Every codec's encode must be shape-polymorphic under vmap over the
    client axis -- traced state only, no Python-int leakage."""

    @pytest.mark.parametrize("n_clients", [1, 3, 5])
    def test_encode_vmaps_over_any_client_count(self, n_clients):
        plan, codecs = _codecs_under_test()
        for codec in codecs:
            cstate = codec.init_client_state(n_clients)
            shared = codec.init_shared_state()
            base = round_base_key(0, 0)
            keys = jax.vmap(
                lambda c, _co=codec: _co.per_client_key(base, c)
            )(jnp.arange(n_clients))
            delta = jax.random.normal(
                jax.random.PRNGKey(3),
                (n_clients, plan.stack) + plan.shape, jnp.float32)
            wire = jax.vmap(codec.to_wire)(delta)

            def enc(cs, k, w, _co=codec, _sh=shared):
                return _co.encode(cs, _sh, k, w)

            cst2, recon, stats = jax.vmap(enc)(cstate, keys, wire)
            assert recon.shape == wire.shape, codec
            assert stats.shape == (n_clients, codec.client_stats_len), codec
            assert stats.dtype == jnp.int32
            red = codec.reduce_stats(stats)
            assert red.shape == (codec.stats_len,), codec
            # state shapes are preserved (so the engine can scatter back)
            for a, b in zip(jax.tree.leaves(cst2), jax.tree.leaves(cstate)):
                assert a.shape == b.shape, codec

    def test_encode_traces_abstractly(self):
        """eval_shape succeeds: no concrete-value dependence inside encode
        (a Python int leaking from traced state would raise here)."""
        plan, codecs = _codecs_under_test()
        for codec in codecs:
            cstate = codec.init_client_state(2)
            shared = codec.init_shared_state()
            wire = jnp.zeros((2, plan.stack, plan.l, plan.m), jnp.float32)
            flat = jnp.zeros((2, plan.raw_scalars), jnp.float32)
            w = wire if isinstance(codec, (SVDFedCodec, GradESTCCodec)) else flat
            key = jax.random.PRNGKey(0)

            def enc(cs, w_, _co=codec, _sh=shared, _k=key):
                return _co.encode(cs, _sh, _k, w_)

            jax.eval_shape(jax.vmap(enc, in_axes=(0, 0)), cstate, w)

    def test_round_trip_reconstruction_shapes(self):
        plan, codecs = _codecs_under_test()
        delta = jax.random.normal(jax.random.PRNGKey(5),
                                  (plan.stack,) + plan.shape, jnp.float32)
        for codec in codecs:
            wire = codec.to_wire(delta)
            back = codec.from_wire(wire, delta.shape)
            assert back.shape == delta.shape
            # to/from wire is an exact (reshape-only) round trip
            np.testing.assert_array_equal(np.asarray(back), np.asarray(delta))


class TestPaddedEncodeKernel:
    """encode_pallas only accepts m % block_m == 0; the ops.encode wrapper
    (and the engine through it) pads via core/reshaping.pad_to_block."""

    @pytest.mark.parametrize("l,k,m", [(96, 8, 100), (64, 4, 37), (256, 16, 200)])
    def test_non_128_multiple_m_matches_einsum(self, l, k, m, key):
        from repro.kernels.ops import encode

        Mq, _ = jnp.linalg.qr(jax.random.normal(key, (l, k), jnp.float32))
        G = jax.random.normal(jax.random.PRNGKey(7), (l, m), jnp.float32)
        A1, E1 = encode(Mq, G, interpret=True)
        A0 = jnp.einsum("lk,lm->km", Mq, G)
        E0 = G - jnp.einsum("lk,km->lm", Mq, A0)
        assert A1.shape == (k, m) and E1.shape == (l, m)
        np.testing.assert_allclose(np.asarray(A1), np.asarray(A0), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(E1), np.asarray(E0), rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("l,k,m", [(96, 8, 100), (64, 4, 37)])
    def test_direct_pallas_call_on_padded_input(self, l, k, m, key):
        from repro.kernels.gradestc_encode import encode_pallas

        Mq, _ = jnp.linalg.qr(jax.random.normal(key, (l, k), jnp.float32))
        G = jax.random.normal(jax.random.PRNGKey(8), (l, m), jnp.float32)
        Gp, m0 = pad_to_block(G, 128, axis=-1)
        assert m0 == m and Gp.shape[-1] % 128 == 0
        A, E = encode_pallas(Mq, Gp, block_m=128, interpret=True)
        A, E = A[:, :m], E[:, :m]
        A0 = jnp.einsum("lk,lm->km", Mq, G)
        np.testing.assert_allclose(np.asarray(A), np.asarray(A0), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(E), np.asarray(G - Mq @ A0),
                                   rtol=1e-4, atol=1e-4)

    def test_pad_to_block_noop_and_zero_fill(self):
        x = jnp.ones((3, 128))
        same, m0 = pad_to_block(x, 128, axis=-1)
        assert same is x and m0 == 128
        padded, m0 = pad_to_block(jnp.ones((3, 100)), 128, axis=-1)
        assert padded.shape == (3, 128) and m0 == 100
        assert float(jnp.abs(padded[:, 100:]).max()) == 0.0
