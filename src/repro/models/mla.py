"""Multi-head latent attention (DeepSeek-V2, arXiv 2405.04434 Sec. 2.1) with
YaRN rotary scaling, as the published ``modeling_deepseek.py`` computes it
without query compression (``q_lora_rank`` null):

  q           = h W_q                 H heads x (nope + rope)
  [c, k_pe]   = h W_kva               kv_lora_rank + rope; k_pe is one key
                                      shared by every head
  c           = RMSNorm(c)
  [k_nope, v] = c W_kvb               H heads x (nope + v)
  q_pe, k_pe  rotated by YaRN-scaled frequencies
  o           = softmax(s [q_nope q_pe] [k_nope k_pe]^T + causal mask) v
  out         = o W_o                 H * v -> d_model

with ``s = (nope + rope)^-1/2 * m^2``, ``m = 0.1 * mscale_all_dim * ln(factor)
+ 1``.  The q/k head (nope + rope) is wider than the v head, so the chunked
attention core (``layers.attention``) takes a narrower v.

Two layouts differ from the checkpoint's and change no function of random
weights: the latent norm's gain is ``(1 + w)`` as every norm of this
package, and the rope dims are rotated in rotate-half order (the published
code first de-interleaves pairs (2i, 2i+1), a fixed permutation of W_q's and
W_kva's rope columns that a checkpoint loader applies once).

Everything here runs under ``jax.named_scope("mla")``, so each compiled op
of the block -- forward, rematerialised and backward -- names it in its
``op_name``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ArchConfig
from .layers import _rotate, attention, rms_norm

Params = Dict[str, Any]

__all__ = ["MLA_SCOPE", "init_mla", "rope_inv_freq", "yarn_mscale",
           "softmax_scale", "project", "mla_attention"]

MLA_SCOPE = "mla"


def init_mla(cfg: ArchConfig, key: jax.Array, L: int) -> Params:
    D, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(D)
    nrm = jax.random.normal
    return {
        "attn_wq": nrm(ks[0], (L, D, H * (nope + rope)), dt) * s,
        "attn_wkv_a": nrm(ks[1], (L, D, r + rope), dt) * s,
        "attn_kv_norm": jnp.zeros((L, r), dt),
        "attn_wkv_b": nrm(ks[2], (L, r, H * (nope + dv)), dt) * (1.0 / math.sqrt(r)),
        "attn_wo": nrm(ks[3], (L, H * dv, D), dt) * (1.0 / math.sqrt(H * dv)),
    }


def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention temperature factor for a context stretched ``scale``
    times."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_inv_freq(cfg: ArchConfig) -> np.ndarray:
    """(rope / 2,) f32 inverse frequencies of the rope dims: plain RoPE's
    below YaRN's low correction dim, divided by ``yarn_factor`` above its
    high one, and a linear ramp between (the published
    ``DeepseekV2YarnRotaryEmbedding``)."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not cfg.yarn_factor:
        return extra.astype(np.float32)

    def correction_dim(rotations):
        return (dim * math.log(cfg.yarn_original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp          # share of the unscaled frequency
    return (extra / cfg.yarn_factor * ramp + extra * keep).astype(np.float32)


def softmax_scale(cfg: ArchConfig) -> float:
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        m = yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
        s *= m * m
    return s


def _rope(cfg: ArchConfig, x: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
    """x: (B, S, h, rope); positions: (B, S)."""
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(rope_inv_freq(cfg))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if cfg.yarn_factor:
        amp = (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
               / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
        if amp != 1.0:
            cos, sin = cos * amp, sin * amp
    return _rotate(x, cos, sin)


def project(cfg: ArchConfig, h: jnp.ndarray, w: Params, positions: jnp.ndarray
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """h: (B, S, D) normed input -> q, k (B, S, H, nope + rope), v (B, S, H,
    v), the rope dims rotated."""
    B, S, _ = h.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q = (h @ w["attn_wq"]).reshape(B, S, H, nope + rope)
    ckv = h @ w["attn_wkv_a"]                                 # (B, S, r + rope)
    c = rms_norm(ckv[..., :r], w["attn_kv_norm"], cfg.norm_eps)
    k_pe = _rope(cfg, ckv[..., None, r:], positions)          # (B, S, 1, rope)
    kv = (c @ w["attn_wkv_b"]).reshape(B, S, H, nope + dv)
    q = jnp.concatenate([q[..., :nope], _rope(cfg, q[..., nope:], positions)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (B, S, H, rope))], -1)
    return q, k, kv[..., nope:]


def mla_attention(cfg: ArchConfig, h: jnp.ndarray, w: Params,
                  positions: jnp.ndarray, window, q_chunk: int = 0
                  ) -> jnp.ndarray:
    """The attention block's output (B, S, D), before the residual add."""
    B, S, _ = h.shape
    with jax.named_scope(MLA_SCOPE):
        q, k, v = project(cfg, h, w, positions)
        o = attention(q, k, v, causal=True, window=window, q_chunk=q_chunk,
                      softmax_scale=softmax_scale(cfg),
                      unroll=cfg.attn_unroll)
        return o.reshape(B, S, -1) @ w["attn_wo"]
