"""Mixture-of-Experts FFN with top-k routing (granite-moe, dbrx,
deepseek-v2).

GShard/Mesh-style capacity-based dispatch expressed as einsums so GSPMD can
partition it: the expert axis E of the weight banks shards over the "model"
mesh axis (expert parallelism), and the dispatch/combine einsums lower to the
expert all-to-all pattern (DESIGN.md Sec. 5).

Tokens are processed in groups (``moe_group``); each group computes a
(S_g, E, C) dispatch one-hot with per-expert capacity
C = ceil(S_g * top_k * capacity_factor / E).  Overflow tokens fall back to
the residual stream (standard capacity-drop semantics).

Expert parallelism's share (``ArchConfig.experts_held``): the layer holds
experts ``first .. first + held - 1`` of ``n_experts``.  The router scores
all ``n_experts`` and the capacity is reckoned over all of them, as on every
chip of the deployment; only the held experts compute, so the layer returns
their part of the routed result, and the parts of all shares add up to the
whole layer.  Shared experts (``n_shared_experts``, DeepSeek-V2) see every
token and are added once.

The router runs in f32 and its weights are *excluded* from GradESTC
compression (tiny but convergence-critical; see core/policy.py).

Named scopes: ``moe_routed`` (router, dispatch and combine one-hots, the
held experts' GEMMs) and ``moe_shared`` (the shared experts) reach every
compiled op's ``op_name``.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .config import ArchConfig
from .layers import swiglu

Params = Dict[str, Any]

__all__ = ["init_moe_ffn", "init_shared_experts", "moe_ffn",
           "router_load_balance_loss", "ROUTED_SCOPE", "SHARED_SCOPE"]

ROUTED_SCOPE = "moe_routed"
SHARED_SCOPE = "moe_shared"

#: tokens per dispatch group; keeps the (S_g, E, C) one-hot bounded.
MOE_GROUP = 4096


def init_moe_ffn(cfg: ArchConfig, key: jax.Array, L: int) -> Params:
    """The router over all ``n_experts`` and the held experts' banks."""
    D, F, E, Eh = cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.held
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    s, sf = 1.0 / math.sqrt(D), 1.0 / math.sqrt(F)
    return {
        "router": jax.random.normal(ks[0], (L, D, E), jnp.float32) * s,
        "moe_wgate": jax.random.normal(ks[1], (L, Eh, D, F), dt) * s,
        "moe_win": jax.random.normal(ks[2], (L, Eh, D, F), dt) * s,
        "moe_wout": jax.random.normal(ks[3], (L, Eh, F, D), dt) * sf,
    }


def init_shared_experts(cfg: ArchConfig, key: jax.Array, L: int) -> Params:
    """The shared experts as one SwiGLU of width ``n_shared_experts * d_ff``."""
    D, Fs = cfg.d_model, cfg.n_shared_experts * cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 3)
    s, sf = 1.0 / math.sqrt(D), 1.0 / math.sqrt(Fs)
    return {
        "shared_wgate": jax.random.normal(ks[0], (L, D, Fs), dt) * s,
        "shared_win": jax.random.normal(ks[1], (L, D, Fs), dt) * s,
        "shared_wout": jax.random.normal(ks[2], (L, Fs, D), dt) * sf,
    }


def _dispatch_one_group(cfg: ArchConfig, x: jnp.ndarray, w: Params,
                        first: int = 0) -> jnp.ndarray:
    """x: (S, D) one token group -> (S, D) output of the held experts."""
    S, D = x.shape
    E, K, Eh = cfg.n_experts, cfg.experts_per_tok, cfg.held
    C = max(1, int(math.ceil(S * K * cfg.capacity_factor / E)))

    logits = x.astype(jnp.float32) @ w["router"]            # (S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)         # (S, K)
    if cfg.norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
        )

    # (S, K, Eh) one-hot of the chosen experts held here: a choice of an
    # expert held elsewhere is a row of zeros, and takes no slot here
    sel = jax.nn.one_hot(expert_idx - first if first else expert_idx, Eh,
                         dtype=jnp.float32)
    # position of each (token, choice) within its expert's buffer:
    # cumulative count over the flattened (choice-major) priority order.
    selk = sel.transpose(1, 0, 2).reshape(K * S, Eh)         # choice-major
    pos_flat = jnp.cumsum(selk, axis=0) - selk               # (K*S, Eh)
    pos = pos_flat.reshape(K, S, Eh).transpose(1, 0, 2)      # (S, K, Eh)
    within_cap = (pos < C) & (sel > 0)

    dt = x.dtype
    slot = jax.nn.one_hot(jnp.sum(pos * sel, axis=-1).astype(jnp.int32), C,
                          dtype=dt)                          # (S, K, C)
    sel_kept = (sel * within_cap).astype(dt)                 # (S, K, Eh)

    # dispatch (S, Eh, C): token s occupies slot c of expert e.  Kept in the
    # model dtype -- these are the largest activations of the MoE block.
    if cfg.moe_stop_gradient_dispatch:
        # The one-hot structure is integer-valued: routing indices carry no
        # gradient, only the gate values do.  Without stop_gradient JAX
        # still materializes (and GSPMD gathers) f32 (S, E, C) cotangents
        # through these einsums -- measured 60 GiB of all-gather on
        # granite-moe train_4k (EXPERIMENTS.md SPerf).  Gate gradients flow
        # through the explicit ge factor below.
        mask = jax.lax.stop_gradient(
            jnp.einsum("ske,skc->sec", sel_kept, slot)
        )
        dispatch = mask
        ge_ = jnp.einsum("ske,sk->se", sel_kept, gate_vals.astype(dt))
        combine = mask * ge_[:, :, None]
    else:
        dispatch = jnp.einsum("ske,skc->sec", sel_kept, slot)
        combine = jnp.einsum(
            "ske,skc->sec", sel_kept * gate_vals[..., None].astype(dt), slot
        )

    xe = jnp.einsum("sec,sd->ecd", dispatch, x)              # (Eh, C, D)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w["moe_wgate"])) * jnp.einsum(
        "ecd,edf->ecf", xe, w["moe_win"]
    )
    ye = jnp.einsum("ecf,efd->ecd", h, w["moe_wout"])        # (Eh, C, D)
    return jnp.einsum("sec,ecd->sd", combine, ye)            # (S, D)


def moe_ffn(cfg: ArchConfig, x: jnp.ndarray, w: Params,
            first: int = 0) -> jnp.ndarray:
    """x: (B, S, D) -> (B, S, D): the held experts' part of the routed
    output (experts ``first ..``), plus the shared experts.  Groups tokens
    to bound dispatch memory."""
    B, S, D = x.shape
    T = B * S
    with jax.named_scope(ROUTED_SCOPE):
        g = min(cfg.moe_group or MOE_GROUP, T)
        while T % g:
            g -= 1
        xg = x.reshape(T // g, g, D)
        yg = jax.vmap(lambda t: _dispatch_one_group(cfg, t, w, first))(xg)
        y = yg.reshape(B, S, D)
    if cfg.n_shared_experts:
        with jax.named_scope(SHARED_SCOPE):
            y = y + swiglu(x, w["shared_wgate"], w["shared_win"],
                           w["shared_wout"])
    return y


def router_load_balance_loss(cfg: ArchConfig, x: jnp.ndarray, w: Params) -> jnp.ndarray:
    """Switch-style auxiliary load-balance loss (mean over layers is applied
    by the training loop when enabled)."""
    T = x.shape[0] * x.shape[1]
    logits = x.reshape(T, -1).astype(jnp.float32) @ w["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    imp = jnp.mean(probs, axis=0)                            # (E,)
    top1 = jax.nn.one_hot(jnp.argmax(probs, axis=-1), cfg.n_experts)
    load = jnp.mean(top1, axis=0)
    return cfg.n_experts * jnp.sum(imp * load)
