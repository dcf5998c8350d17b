"""The ledger checks of chip_smoke.py, run on the CPU.

On the CPU the fused engine and the reference loop ship identical GradESTC
updates, so the smoke's Formula-14 recomputation must reproduce both
ledgers exactly, and a charge that disagrees with the shipped d_r must be
refused while a trajectory that ships different d_r is not.
"""

import copy
import dataclasses
import importlib.util
import pathlib

import pytest

from repro.core.policy import make_policy
from repro.fl import FLConfig, run_fl
from repro.models import param_group_shapes
from repro.models.config import ArchConfig

_SPEC = importlib.util.spec_from_file_location(
    "chip_smoke", pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py")
smoke = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(smoke)


#: one layer, narrow widths: the checks read only the policy's plans, and
#: a small model keeps the two engines' compile time down
_ARCH = ArchConfig(name="smoke-check", family="dense", n_layers=1,
                   d_model=32, n_heads=2, n_kv_heads=2, d_ff=64, vocab=64,
                   dtype="float32", remat=False, attn_chunk=0)


def _cfg(**method_kw):
    return FLConfig(method="gradestc", method_kw=method_kw, arch=_ARCH,
                    rounds=4, n_clients=2, local_steps=1, batch=2, seq=8,
                    eval_every=3, min_params=2048, seed=1, use_pallas=False)


@pytest.fixture(scope="module")
def runs():
    cfg = _cfg()
    return ("gradestc", cfg, run_fl(cfg),
            run_fl(dataclasses.replace(cfg, engine="loop")))


def test_formula14_reproduces_ledger(runs):
    _, cfg, fused, loop = runs
    for res in (fused, loop):
        assert len(res.extra["uplink_stats"]) == cfg.rounds
        assert res.ledger.per_round_uplink_bits == [
            smoke.formula14_bits(st, cfg) for st in res.extra["uplink_stats"]]
    assert fused.extra["uplink_stats"] == loop.extra["uplink_stats"]


def test_formula14_reproduces_int8_ledger():
    cfg = _cfg(wire_dtype="int8")
    res = run_fl(cfg)
    assert res.ledger.per_round_uplink_bits == [
        smoke.formula14_bits(st, cfg) for st in res.extra["uplink_stats"]]


def test_compare_ledgers_exact_on_cpu(runs):
    label, cfg, fused, loop = runs
    assert smoke.compare_ledgers(label, cfg, fused, loop, "fused",
                                 "loop") == "exact"


def _with_extra_vector(cfg, res, rnd, charge=True):
    """A copy of ``res`` whose round ``rnd`` shipped one more entering
    vector in its first compressed group, charged (l + 1 f32 scalars: the
    vector and its index) or not."""
    out = copy.deepcopy(res)
    stats = out.extra["uplink_stats"][rnd]
    path = sorted(stats)[0]
    st = list(stats[path])
    st[2] += 1
    stats[path] = tuple(st)
    if charge:
        plan = make_policy(param_group_shapes(cfg.arch),
                           overrides=cfg.policy_overrides,
                           coverage_target=cfg.coverage_target,
                           min_params=cfg.min_params).plans[path]
        out.ledger.per_round_uplink_bits[rnd] += 32 * (plan.l + 1)
    return out


def test_compare_ledgers_follows_shipped_vectors(runs):
    label, cfg, fused, loop = runs
    later = _with_extra_vector(cfg, fused, cfg.rounds - 1)
    assert "parted@round" in smoke.compare_ledgers(label, cfg, later, loop,
                                                   "fused", "loop")


@pytest.mark.parametrize("case", ["uncharged", "early", "other-method"])
def test_compare_ledgers_refuses(runs, case):
    label, cfg, fused, loop = runs
    if case == "uncharged":       # a vector shipped but not charged
        bad = _with_extra_vector(cfg, fused, cfg.rounds - 1, charge=False)
    elif case == "early":         # trajectories may not part this soon
        bad = _with_extra_vector(cfg, fused, 1)
    else:                         # only GradESTC's charge may differ
        bad, label = _with_extra_vector(cfg, fused, cfg.rounds - 1), "svdfed"
    with pytest.raises(SystemExit):
        smoke.compare_ledgers(label, cfg, bad, loop, "fused", "loop")
