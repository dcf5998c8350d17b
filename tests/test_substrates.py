"""Substrate tests: optimizers, schedules, data pipeline, checkpointing,
compression policy, and the launch-layer delta<->matrix plumbing."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint as ckpt
from repro.core.policy import LayerPlan, make_policy, coverage
from repro.data import client_batch_stream, make_task
from repro.data.partition import dirichlet_client_priors, iid_client_priors
from repro.optim import adam, cosine_decay, constant, linear_warmup, sgd


class TestOptim:
    def _quad(self, opt_init, opt_update, steps=200):
        params = {"x": jnp.asarray([3.0, -2.0])}
        st = opt_init(params)
        for _ in range(steps):
            g = jax.grad(lambda p: jnp.sum(p["x"] ** 2))(params)
            params, st = opt_update(g, st, params)
        return float(jnp.abs(params["x"]).max())

    def test_sgd_converges(self):
        assert self._quad(*sgd(0.1)) < 1e-3

    def test_sgd_momentum_converges(self):
        assert self._quad(*sgd(0.05, momentum=0.9)) < 1e-3

    def test_adam_converges(self):
        assert self._quad(*adam(0.1)) < 1e-2

    def test_schedules(self):
        s = cosine_decay(1.0, 100, warmup_steps=10)
        assert float(s(jnp.asarray(0))) == 0.0
        assert float(s(jnp.asarray(10))) == pytest.approx(1.0, abs=1e-3)
        assert float(s(jnp.asarray(100))) < 0.01
        w = linear_warmup(2.0, 4)
        assert float(w(jnp.asarray(2))) == pytest.approx(1.0)
        assert float(constant(0.3)(jnp.asarray(7))) == pytest.approx(0.3)


class TestData:
    def test_priors(self):
        p = iid_client_priors(5, 8)
        np.testing.assert_allclose(p.sum(1), 1.0)
        d = dirichlet_client_priors(5, 8, 0.1)
        np.testing.assert_allclose(d.sum(1), 1.0, rtol=1e-5)
        # low alpha -> skewed
        assert d.max() > 0.5

    def test_stream_shapes_and_determinism(self):
        task = make_task(vocab=64, n_clients=3, alpha=0.5, seed=3)
        s1 = client_batch_stream(task, 0, 4, 16, seed=9)
        s2 = client_batch_stream(task, 0, 4, 16, seed=9)
        b1, b2 = next(s1), next(s2)
        assert b1["tokens"].shape == (4, 16)
        np.testing.assert_array_equal(np.asarray(b1["tokens"]), np.asarray(b2["tokens"]))
        # labels are next tokens
        x1 = next(s1)
        assert x1["tokens"].shape == x1["labels"].shape

    def test_clients_differ_under_noniid(self):
        task = make_task(vocab=64, n_clients=3, alpha=0.1, seed=3)
        b0 = next(client_batch_stream(task, 0, 8, 64, seed=1))
        b1 = next(client_batch_stream(task, 1, 8, 64, seed=1))
        h0 = np.bincount(np.asarray(b0["tokens"]).ravel(), minlength=64)
        h1 = np.bincount(np.asarray(b1["tokens"]).ravel(), minlength=64)
        # token histograms materially different
        assert np.abs(h0 - h1).sum() > 0.2 * h0.sum()

    def test_chain_is_learnable(self):
        """The transition structure must be sharp enough to learn."""
        task = make_task(vocab=64, n_clients=2, seed=0)
        ent = -np.sum(task.trans * np.log(task.trans + 1e-12), axis=1).mean()
        assert ent < 0.7 * np.log(64)


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        tree = {
            "layers": {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4)},
            "opt": (jnp.zeros(3, jnp.bfloat16), jnp.asarray(7)),
        }
        path = str(tmp_path / "ck")
        ckpt.save(path, 42, tree)
        assert ckpt.latest_step(path) == 42
        out = ckpt.restore(path, 42, tree)
        np.testing.assert_array_equal(
            np.asarray(out["layers"]["w"]), np.asarray(tree["layers"]["w"]))
        assert out["opt"][0].dtype == jnp.bfloat16
        assert int(out["opt"][1]) == 7

    def test_atomic_overwrite(self, tmp_path):
        path = str(tmp_path / "ck")
        ckpt.save(path, 1, {"a": jnp.ones(4)})
        ckpt.save(path, 2, {"a": jnp.ones(4) * 2})
        assert ckpt.latest_step(path) == 2
        out = ckpt.restore(path, 2, {"a": jnp.zeros(4)})
        np.testing.assert_array_equal(np.asarray(out["a"]), 2 * np.ones(4))


class TestPolicy:
    def test_parameter_dominant_selection(self):
        shapes = {
            "big": ((1024, 1024), 8),
            "small": ((64, 64), 8),
            "embed": ((5000, 64), 1),
            "norm": ((64,), 9),
        }
        p = make_policy(shapes, min_params=1000)
        assert p.plans["big"].compress
        assert not p.plans["embed"].compress      # excluded by name
        assert not p.plans["norm"].compress
        assert coverage(p) > 0.5

    def test_formula14_scalars(self):
        lp = LayerPlan(name="g", shape=(256, 512), stack=4, l=512, m=256,
                       k=16, compress=True)
        assert lp.update_scalars(d_r=3) == (16 * 256 + 3 * 512 + 3) * 4
        assert lp.init_scalars == (16 * 512 + 16 * 256) * 4
        assert lp.raw_scalars == 256 * 512 * 4


class TestLaunchPlumbing:
    """_delta_to_G / _G_to_delta must be exact inverses for every plan."""

    @pytest.mark.parametrize("shape,l", [
        ((64, 48), 48), ((64, 48), 64), ((8, 32, 16), 32), ((8, 32, 16), 16),
        ((128, 96), 32),   # l not a tensor dim -> generic segment path
    ])
    def test_roundtrip(self, shape, l):
        from repro.launch.steps import _delta_to_G, _G_to_delta
        n = int(np.prod(shape))
        lp = LayerPlan(name="t", shape=shape, stack=3, l=l, m=n // l,
                       k=4, compress=True)
        rng = np.random.default_rng(0)
        delta = jnp.asarray(rng.normal(size=(2, 3) + shape), jnp.float32)
        G = _delta_to_G(delta, lp)
        assert G.shape == (2, 3, l, n // l)
        back = _G_to_delta(G, lp, delta.shape)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(delta))


class TestCompileCacheDir:
    """One fixed cache path: the environment's when set, else the
    checkout's gitignored .jax_cache -- never a directory of its own."""

    def test_env_unset_uses_checkout_dir(self, monkeypatch):
        from repro.launch import compile_cache as cc
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert cc.compilation_cache_dir() == os.path.join(root, ".jax_cache")

    def test_env_set_is_used_verbatim(self, monkeypatch, tmp_path):
        from repro.launch import compile_cache as cc
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert cc.compilation_cache_dir() == str(tmp_path)
