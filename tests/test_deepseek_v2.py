"""DeepSeek-V2 (``family: "deepseek_v2"``) against its plain reference, at a
small size on the CPU, in float32.

* the program's loss and gradients against the benchmark's plain reference
  (``benchmarks/chip/models/deepseek_v2.py``, which imports nothing of the
  program) on the same seeded weights;
* the expert share: the parts that the shares of a layer's experts give,
  with the shared experts counted once, add up to the uncut layer, and the
  uncut layer is the reference's;
* YaRN's frequencies and softmax scale at the published numbers, by hand;
* a fused GradESTC ``run_fl`` on the family charges exactly Formula 14;
* the benchmark's configuration file: every key is the program's, or the
  source's ``config.json`` key that the program's key restates.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import deepseek_v2_lite
from repro.models import mla, model, moe
from repro.models.layers import swiglu
from repro.models.config import ArchConfig

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(ROOT / "benchmarks/chip/models/deepseek_v2.py", "ref_deepseek_v2")
SMOKE = _load(ROOT / "chip_smoke.py", "chip_smoke")

#: the small model: 1 dense + 2 expert layers, 16 routed experts top-4 with
#: the gates as published (not renormalised), 2 shared, YaRN as published
TINY = dict(
    name="dsv2-tiny", family="deepseek_v2", n_layers=3, d_model=64, n_heads=4,
    n_kv_heads=4, d_ff=32, vocab=96, n_experts=16, experts_per_tok=4,
    experts_held=16, capacity_factor=1.25, moe_group=4096,
    norm_topk_prob=False, n_shared_experts=2, first_k_dense_replace=1,
    dense_d_ff=96, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, rope_theta=10000.0, yarn_factor=40.0,
    yarn_original_max_position=4096, yarn_beta_fast=32.0, yarn_beta_slow=1.0,
    yarn_mscale=0.707, yarn_mscale_all_dim=0.707, norm_eps=1e-6,
    dtype="float32", remat=True, attn_chunk=8, ce_chunk=8)
F32 = jnp.float32


def _arch(**kw) -> ArchConfig:
    return ArchConfig(**dict(TINY, **kw))


def _batch(B=2, S=16, seed=1):
    t = jax.random.randint(jax.random.PRNGKey(seed), (B, S + 1), 0, TINY["vocab"])
    return {"tokens": t[:, :-1], "labels": t[:, 1:]}


# ---------------------------------------------------------------------------
# the program against the plain reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("held", [16, 8])
def test_loss_and_grads_match_reference(held):
    """The whole model, its every expert held or an 8-of-16 share (as the
    benchmark's cell holds 8 of 64), with query chunks and remat on."""
    arch = _arch(experts_held=held)
    cfg = dataclasses.asdict(arch)
    key = jax.random.PRNGKey(3)
    params = model.init_params(arch, key)
    ref_params = REF.init(cfg, key)
    assert jax.tree.structure(params) == jax.tree.structure(ref_params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(ref_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    batch = _batch()
    got_l, got_g = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(arch, p, batch)))(params)
    with jax.default_matmul_precision("highest"):
        want_l, want_g = jax.jit(jax.value_and_grad(
            lambda p: REF.loss(cfg, p, batch, F32)))(params)
    # f32 on both sides; the sums run in another order (query chunks,
    # capacity buffers, chunked CE), so they agree to f32 rounding
    np.testing.assert_allclose(float(got_l), float(want_l), rtol=2e-6)
    flat_g = jax.tree_util.tree_flatten_with_path(got_g)[0]
    for (path, g), w in zip(flat_g, jax.tree.leaves(want_g)):
        scale = float(jnp.max(jnp.abs(w))) + 1e-12
        err = float(jnp.max(jnp.abs(g - w))) / scale
        # 1e-4 of the leaf's largest entry: f32 rounding of sums over up to
        # 32 tokens and 96 features, far below any dropped term
        assert err < 1e-4, (jax.tree_util.keystr(path), err)
        assert scale > 1e-8, jax.tree_util.keystr(path)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four shares of 4 experts each: their parts of the routed output, plus
    the shared experts once, are the uncut layer, which is the reference's
    (a capacity of every token, so no choice drops)."""
    full = _arch(capacity_factor=4.0)       # C = T * k * 4 / 16 = T
    share = dataclasses.replace(full, experts_held=4, n_shared_experts=0)
    params = model.init_params(full, jax.random.PRNGKey(5))
    w = {k: v[0] for k, v in params["layers"].items()
         if k == "router" or k.startswith(("moe_", "shared_"))}
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 16, TINY["d_model"]), F32)
    whole = moe.moe_ffn(full, x, w)
    parts = []
    for j in range(4):
        wj = dict(w, **{k: w[k][4 * j:4 * j + 4]
                        for k in ("moe_wgate", "moe_win", "moe_wout")})
        parts.append(moe.moe_ffn(share, x, wj, first=4 * j))
    shared = swiglu(x, w["shared_wgate"], w["shared_win"], w["shared_wout"])
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), rtol=1e-5, atol=1e-6)
    for part in parts:               # every share computes something here
        assert float(jnp.max(jnp.abs(part))) > 0
    with jax.default_matmul_precision("highest"):
        want = REF._experts(dataclasses.asdict(full), x, w, F32)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_yarn_at_published_numbers():
    """DeepSeek-V2-Lite's rope dims: 64, theta 1e4, factor 40 over 4096
    positions, beta 32 / 1, mscale = mscale_all_dim = 0.707."""
    cfg = deepseek_v2_lite.CONFIG
    # correction dims 64 ln(4096 / (2 pi beta)) / (2 ln 1e4): 10.47 for
    # beta 32 (floor 10), 22.51 for beta 1 (ceil 23)
    i = np.arange(32)
    extra = 10000.0 ** (-2.0 * i / 64)
    ramp = np.clip((i - 10) / 13.0, 0, 1)
    want = extra * (1 - ramp) + extra / 40.0 * ramp
    got = mla.rope_inv_freq(cfg)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[9] == pytest.approx(extra[9]) and got[23] == pytest.approx(extra[23] / 40)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert mla.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    ref_freq, amp, ref_scale = REF.yarn(dataclasses.asdict(cfg))
    np.testing.assert_array_equal(ref_freq, got)
    assert amp == 1.0 and ref_scale == pytest.approx(mla.softmax_scale(cfg))


# ---------------------------------------------------------------------------
# the normal path
# ---------------------------------------------------------------------------

def test_fused_gradestc_run_charges_formula14():
    """Two fused GradESTC rounds: the ledger charges exactly Formula 14 of
    what each round shipped, and the latent attention, expert, shared and
    dense banks are among the compressed groups."""
    from repro.core.policy import make_policy
    from repro.fl import FLConfig, run_fl
    from repro.models import param_group_shapes

    arch = _arch(experts_held=8, remat=False)
    cfg = FLConfig(method="gradestc", arch=arch, rounds=2, n_clients=2,
                   local_steps=1, batch=2, seq=8, eval_every=1,
                   min_params=2048, seed=2, use_pallas=False)
    res = run_fl(cfg)
    assert res.extra["engine"] == "fused"
    assert res.ledger.per_round_uplink_bits == [
        SMOKE.formula14_bits(st, cfg) for st in res.extra["uplink_stats"]]
    plans = make_policy(param_group_shapes(arch), min_params=2048).plans
    compressed = {p for p, pl in plans.items() if pl.compress}
    assert {"layers/attn_wkv_b", "layers/moe_wgate", "layers/shared_win",
            "layers/dense_mlp_wgate"} <= compressed
    assert not {p for p in compressed if "router" in p or "norm" in p}
    assert all(np.isfinite(res.eval_loss))


# ---------------------------------------------------------------------------
# the benchmark's configuration file
# ---------------------------------------------------------------------------

#: keys of the file that describe the file, not the model
META = {"name", "source", "program_config", "reduced", "deployment", "assumed"}


def test_benchmark_config_keys_are_the_programs():
    """A key the program does not know would be dropped without a word
    (``fedbench.bench.fl_config``).  So every key of the file is an
    ``ArchConfig`` field or a key of the source's ``config.json``, and the
    source's keys, read by the preset's own rule, give the very
    configuration the program's keys give."""
    path = ROOT / "benchmarks/chip/configs/deepseek-v2-lite.5l.json"
    model_cfg = json.loads(path.read_text())
    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    published = deepseek_v2_lite.PUBLISHED
    unknown = set(model_cfg) - META - fields - set(published)
    assert not unknown, unknown
    assert set(published) <= set(model_cfg)
    for key, value in published.items():      # cut only where stated
        if key not in model_cfg["reduced"]:
            assert model_cfg[key] == value, key
    arch = ArchConfig(**{k: v for k, v in model_cfg.items() if k in fields})
    source_keys = {k: model_cfg[k] for k in published}
    # the file's n_routed_experts counts the experts held here; the router
    # keeps the published count
    source_keys["n_routed_experts"] = (
        model_cfg["reduced"]["n_routed_experts"]["published"])
    want = deepseek_v2_lite.from_published(
        source_keys, experts_held=model_cfg["n_routed_experts"])
    for f in fields - {"name", "source"}:
        assert getattr(arch, f) == getattr(want, f), f
    assert model_cfg["seq_aux"] is False       # no balance loss is added
