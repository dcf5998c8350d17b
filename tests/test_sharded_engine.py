"""Sharded fused round engine vs single-device fused vs loop (DESIGN.md
Sec. 10).

The sharded engine must be **ledger-exact** against the single-device fused
program (and, transitively, the reference loop): identical uplink/downlink
byte counts for every method, eval-loss trajectories to float tolerance.
The full matrix runs in the CI multi-device job
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``); on a plain
single-device run, a subprocess smoke test keeps the sharded path covered.
"""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import metrics
from repro.fl import FLConfig, run_fl

# all seven Table III methods with their per-method loss tolerances --
# shared with the fused-vs-loop parity matrix so the two suites cannot
# silently enforce different bars (byte accounting is exactly equal in
# every case regardless).
from test_round_engine import METHODS

NDEV = len(jax.devices())
needs8 = pytest.mark.skipif(
    NDEV < 8,
    reason="needs 8 host-platform devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _cfg(**kw):
    base = dict(method="gradestc", rounds=4, n_clients=8, local_steps=1,
                batch=4, seq=16, eval_every=2, seed=1)
    base.update(kw)
    return FLConfig(**base)


def _assert_parity(shard, ref, atol=1e-5):
    np.testing.assert_allclose(shard.eval_loss, ref.eval_loss, rtol=0,
                               atol=atol)
    # the acceptance bar: sharding must not move a single ledger byte
    assert shard.ledger.per_round_uplink == ref.ledger.per_round_uplink
    assert shard.ledger.uplink_total == ref.ledger.uplink_total
    assert shard.ledger.downlink_total == ref.ledger.downlink_total
    assert shard.uplink_bytes == ref.uplink_bytes
    assert shard.extra.get("sum_d") == ref.extra.get("sum_d")


@needs8
class TestShardedParity:
    @pytest.mark.parametrize("method,atol", METHODS)
    def test_all_methods_ledger_exact(self, method, atol):
        single = run_fl(_cfg(method=method))
        shard = run_fl(_cfg(method=method, devices=8))
        assert shard.extra["devices"] == 8
        _assert_parity(shard, single, atol)

    def test_sharded_vs_loop(self):
        """Transitivity guard: the sharded engine pins directly to the
        reference loop, not only to the single-device fused program."""
        loop = run_fl(_cfg(engine="loop"))
        shard = run_fl(_cfg(devices=8))
        _assert_parity(shard, loop)

    def test_nondivisible_client_count_padding(self):
        """n_sel=6 on an 8-way mesh: two padding lanes mirror client sel[0]
        and are masked out of the mean/stats; bytes stay exact."""
        kw = dict(n_clients=10, participation=0.6)
        single = run_fl(_cfg(**kw))
        shard = run_fl(_cfg(devices=8, **kw))
        assert single.extra["devices"] == 1
        _assert_parity(shard, single)

    def test_partial_participation_mixed_mode(self):
        """Stragglers initializing late (mixed cond rounds) under sharding."""
        kw = dict(n_clients=12, participation=0.5, rounds=5)
        single = run_fl(_cfg(**kw))
        shard = run_fl(_cfg(devices=8, **kw))
        _assert_parity(shard, single)

    def test_downlink_codec_sharded(self):
        kw = dict(downlink_compress=True)
        single = run_fl(_cfg(**kw))
        shard = run_fl(_cfg(devices=8, **kw))
        _assert_parity(shard, single)

    def test_scan_chunks_sharded_parity(self):
        """The K-round scan chunk under shard_map: same trajectory and
        ledger as K=1 sharded and as the single-device scan -- and zero
        mid-run recompiles (one executable per chunk shape)."""
        single = run_fl(_cfg(rounds=6, scan_rounds=4))
        shard1 = run_fl(_cfg(rounds=6, devices=8, scan_rounds=1))
        shardk = run_fl(_cfg(rounds=6, devices=8, scan_rounds=4))
        _assert_parity(shardk, single, atol=1e-5)
        _assert_parity(shardk, shard1, atol=1e-7)
        assert shardk.extra["chunks"] < shard1.extra["chunks"]
        assert shardk.extra["chunk_compiles"] == shardk.extra["chunk_shapes"]

    def test_single_host_sync_per_chunk_sharded(self):
        """The per-chunk host-sync contract survives shard_map: one packed
        stats fetch per K-round chunk, plus one fetch per eval round."""
        rounds = 6
        metrics.reset_host_sync_count()
        res = run_fl(_cfg(rounds=rounds, devices=8, eval_every=100,
                          scan_rounds=4))
        assert res.extra["chunks"] == 3       # (0,1), (1,5), (5,6)
        assert metrics.host_sync_count() == (res.extra["chunks"]
                                             + len(res.eval_rounds))


class TestShardedSubprocessSmoke:
    """Keeps the sharded path exercised by the plain (single-device) suite:
    a child process forces 4 host devices and asserts fused-sharded vs
    fused-single parity on a tiny model."""

    @pytest.mark.skipif(NDEV >= 8, reason="covered by TestShardedParity")
    def test_sharded_parity_in_subprocess(self):
        child = r"""
import numpy as np
from repro.fl import FLConfig, run_fl
from repro.models.config import ArchConfig
arch = ArchConfig(name="t", family="dense", n_layers=2, d_model=32,
                  n_heads=2, n_kv_heads=2, d_ff=128, vocab=64,
                  dtype="float32", remat=False, attn_chunk=0)
kw = dict(method="gradestc", rounds=4, n_clients=6, local_steps=1, batch=2,
          seq=16, eval_every=2, seed=1, arch=arch)
a = run_fl(FLConfig(engine="fused", **kw))
b = run_fl(FLConfig(engine="fused", devices=4, scan_rounds=3, **kw))
np.testing.assert_allclose(b.eval_loss, a.eval_loss, rtol=0, atol=1e-5)
assert b.ledger.per_round_uplink == a.ledger.per_round_uplink
assert b.ledger.uplink_total == a.ledger.uplink_total
print("SHARDED-PARITY-OK")
"""
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=4"
                            ).strip()
        src = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src")
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        out = subprocess.run([sys.executable, "-c", child], env=env,
                             capture_output=True, text=True, timeout=900)
        assert out.returncode == 0, out.stderr[-3000:]
        assert "SHARDED-PARITY-OK" in out.stdout
