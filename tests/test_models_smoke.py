"""Per-architecture smoke tests (assignment contract): instantiate the
REDUCED variant of each family (<= 2 layers, d_model <= 512, <= 4 experts),
run one forward and one train step on CPU, assert shapes + no NaNs."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import arch_names, get_config
from repro.models import model

ARCHS = arch_names()


@pytest.fixture(scope="module")
def built():
    """Build each reduced arch once per module."""
    cache = {}

    def get(name):
        if name not in cache:
            cfg = get_config(name).reduced()
            params = model.init_params(cfg, jax.random.PRNGKey(0))
            cache[name] = (cfg, params)
        return cache[name]

    return get


def _batch(cfg, B=2, S=16):
    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    batch.update(model.extra_inputs(cfg, B, S))
    return batch


@pytest.mark.parametrize("name", ARCHS)
def test_reduced_config_contract(name):
    cfg = get_config(name).reduced()
    assert cfg.n_layers <= max(2, len(cfg.pattern))
    assert cfg.d_model <= 512
    assert cfg.n_experts <= 4


@pytest.mark.parametrize("name", ARCHS)
def test_forward_shapes_no_nan(name, built):
    cfg, params = built(name)
    B, S = 2, 16
    batch = _batch(cfg, B, S)
    logits = model.forward(cfg, params, batch)
    S_out = S + (cfg.vision_tokens if cfg.family == "vlm" else 0)
    assert logits.shape == (B, S_out, cfg.vocab)
    assert not np.isnan(np.asarray(logits)).any()


@pytest.mark.parametrize("name", ARCHS)
def test_one_train_step_improves_or_finite(name, built):
    cfg, params = built(name)
    batch = _batch(cfg)
    loss0, grads = jax.value_and_grad(
        lambda p: model.loss_fn(cfg, p, batch)
    )(params)
    assert np.isfinite(float(loss0))
    gnorm = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0
    p2 = jax.tree.map(lambda p, g: p - 0.05 * g.astype(p.dtype), params, grads)
    loss1 = float(model.loss_fn(cfg, p2, batch))
    assert np.isfinite(loss1)
    assert loss1 < float(loss0) + 0.5   # step on same batch shouldn't explode


@pytest.mark.parametrize("name", ARCHS)
def test_decode_step_shapes(name, built):
    cfg, params = built(name)
    B = 2
    batch = _batch(cfg, B, 8)
    if cfg.family == "encdec":
        from repro.models import encdec
        enc_out = encdec.encode_audio(cfg, params, batch["audio_frames"])
        cache = model.init_cache(cfg, B, 32, enc_out=enc_out, params=params)
    else:
        cache = model.init_cache(cfg, B, 32)
    lg, cache2 = model.decode_step(cfg, params, cache, batch["tokens"][:, :1])
    assert lg.shape == (B, 1, cfg.vocab)
    assert not np.isnan(np.asarray(lg)).any()
    assert int(cache2.length) == int(cache.length) + 1


@pytest.mark.parametrize("name", [n for n in ARCHS if n not in
                                  ("qwen2-vl-72b",)])  # vlm prefix shifts positions
def test_decode_matches_forward(name, built):
    """KV-cache decode must reproduce the full forward logits."""
    cfg, params = built(name)
    if cfg.n_experts:
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)  # no capacity drops
        params = model.init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 8
    batch = _batch(cfg, B, S)
    logits = model.forward(cfg, params, batch)[:, -S:, :]
    if cfg.family == "encdec":
        from repro.models import encdec
        enc_out = encdec.encode_audio(cfg, params, batch["audio_frames"])
        cache = model.init_cache(cfg, B, S + 2, enc_out=enc_out, params=params)
    else:
        cache = model.init_cache(cfg, B, S + 2)
    outs = []
    for t in range(S):
        lg, cache = model.decode_step(cfg, params, cache, batch["tokens"][:, t:t + 1])
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    scale = float(jnp.abs(logits).max()) + 1e-9
    assert float(jnp.abs(dec - logits).max()) / scale < 2e-2


def test_moe_capacity_drops_are_the_only_decode_divergence():
    """With generous capacity, MoE decode matches training exactly."""
    cfg = dataclasses.replace(get_config("granite-moe-1b-a400m").reduced(),
                              capacity_factor=8.0)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, 2, 8)
    logits = model.forward(cfg, params, batch)
    cache = model.init_cache(cfg, 2, 10)
    outs = []
    for t in range(8):
        lg, cache = model.decode_step(cfg, params, cache, batch["tokens"][:, t:t+1])
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(logits), atol=1e-3)


@pytest.mark.parametrize("name", ["granite-moe-1b-a400m", "deepseek-v2-lite"])
def test_param_group_shapes_cover_every_leaf(name, built):
    """The FL policy and the server step see only the listed groups, so
    every leaf ``init_params`` returns is listed, with its per-layer shape
    and its stack (a leaf of ``layers`` stacks on its leading axis)."""
    cfg, params = built(name)
    leaves = {}
    for path, x in jax.tree_util.tree_flatten_with_path(params)[0]:
        keys = [p.key for p in path]
        if keys[0] == "layers":
            leaves["/".join(keys)] = (tuple(x.shape[1:]), x.shape[0])
        else:
            leaves["/".join(keys)] = (tuple(x.shape), 1)
    assert model.param_group_shapes(cfg) == leaves


def test_gemma3_local_global_pattern():
    cfg = get_config("gemma3-1b")
    kinds = cfg.layer_kinds()
    assert kinds.count("global") == 26 // 6 + (1 if 26 % 6 == 0 else 0)
    assert all(k == "global" for i, k in enumerate(kinds) if (i % 6) == 5)


def test_recurrentgemma_pattern_counts():
    cfg = get_config("recurrentgemma-9b")
    kinds = cfg.layer_kinds()
    assert len(kinds) == 38
    assert kinds.count("rec") == 26 and kinds.count("local") == 12


def test_chunked_loss_matches_full():
    """ce_chunk must not change the loss value."""
    cfg = get_config("tinyllama-1.1b").reduced()
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, 2, 32)
    l_chunked = float(model.loss_fn(cfg, params, batch))
    cfg_full = dataclasses.replace(cfg, ce_chunk=32)
    l_full = float(model.loss_fn(cfg_full, params, batch))
    np.testing.assert_allclose(l_chunked, l_full, rtol=1e-5)


class TestPerfSwitches:
    """SPerf hillclimb switches must preserve semantics (EXPERIMENTS.md)."""

    def test_gqa_native_bit_exact(self):
        cfg0 = get_config("llama3-8b").reduced()
        cfg1 = dataclasses.replace(cfg0, gqa_native=True)
        p = model.init_params(cfg0, jax.random.PRNGKey(0))
        batch = _batch(cfg0, 2, 16)
        l0 = model.forward(cfg0, p, batch)
        l1 = model.forward(cfg1, p, batch)
        np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))

    def test_moe_stop_gradient_dispatch_semantics(self):
        cfg0 = get_config("granite-moe-1b-a400m").reduced()
        cfg1 = dataclasses.replace(cfg0, moe_stop_gradient_dispatch=True)
        p = model.init_params(cfg0, jax.random.PRNGKey(0))
        batch = _batch(cfg0, 2, 16)
        l0 = float(model.loss_fn(cfg0, p, batch))
        l1 = float(model.loss_fn(cfg1, p, batch))
        assert abs(l0 - l1) < 1e-6
        g0 = jax.grad(lambda pp: model.loss_fn(cfg0, pp, batch))(p)
        g1 = jax.grad(lambda pp: model.loss_fn(cfg1, pp, batch))(p)
        # router gradients identical: the one-hot path carries zero gradient
        np.testing.assert_allclose(
            np.asarray(g0["layers"]["router"]),
            np.asarray(g1["layers"]["router"]), rtol=1e-5, atol=1e-7)

    def test_pad_vocab_shapes_and_loss_masking(self):
        cfg0 = dataclasses.replace(get_config("granite-moe-1b-a400m").reduced(),
                                   vocab=515)
        cfgp = dataclasses.replace(cfg0, pad_vocab_multiple=16)
        p = model.init_params(cfgp, jax.random.PRNGKey(0))
        assert p["embed"].shape[0] == 528            # padded
        batch = _batch(cfgp, 2, 16)
        logits = model.forward(cfgp, p, batch)
        assert logits.shape[-1] == 515               # sliced back
        loss = float(model.loss_fn(cfgp, p, batch))
        assert np.isfinite(loss)
        g = jax.grad(lambda pp: model.loss_fn(cfgp, pp, batch))(p)
        gn = sum(float(jnp.abs(x).sum()) for x in jax.tree.leaves(g))
        assert np.isfinite(gn) and gn > 0
