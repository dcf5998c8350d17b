"""Shared neural-net layers: norms, RoPE / M-RoPE, GQA attention, SwiGLU.

Everything is a pure function over explicit parameter pytrees (no framework
modules), so stacks can be scanned/vmapped and sharded with pjit directly.

Numerical policy: parameters and activations in the config dtype (bf16 for
production configs), normalization statistics and softmax in f32.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "rms_norm", "layer_norm",
    "rope_table", "apply_rope", "apply_mrope",
    "attention", "decode_attention", "repeat_kv",
    "swiglu", "gelu_mlp",
    "KVCache",
]


# --------------------------------------------------------------------------
# normalization
# --------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    rrms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return ((x32 * rrms) * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# rotary positions
# --------------------------------------------------------------------------

def rope_table(positions: jnp.ndarray, head_dim: int, theta: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for given positions.  positions: (..., S) int32.
    Returns (cos, sin) of shape (..., S, head_dim//2), f32."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs     # (..., S, half)
    return jnp.cos(ang), jnp.sin(ang)


def _rotate(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: (B, S, H, hd); cos/sin: (B, S, half) broadcast over heads."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :].astype(jnp.float32)
    s = sin[..., None, :].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([x1f * c - x2f * s, x2f * c + x1f * s], axis=-1).astype(x.dtype)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Standard RoPE.  x: (B, S, H, hd); positions: (B, S) or (S,)."""
    if positions.ndim == 1:
        positions = positions[None, :]
    cos, sin = rope_table(positions, x.shape[-1], theta)
    return _rotate(x, cos, sin)


def apply_mrope(
    x: jnp.ndarray, positions3: jnp.ndarray, theta: float,
    sections: Tuple[float, float, float] = (0.25, 0.375, 0.375),
) -> jnp.ndarray:
    """Qwen2-VL multimodal RoPE [arXiv:2409.12191].

    The rotary frequency dims are split into three contiguous sections fed by
    (temporal, height, width) position ids.  positions3: (3, B, S).
    For pure text the three id streams are identical, recovering 1-D RoPE.
    """
    half = x.shape[-1] // 2
    s0 = int(half * sections[0])
    s1 = int(half * sections[1])
    bounds = (s0, s0 + s1)
    cos_parts, sin_parts = [], []
    lo = 0
    for i, hi in enumerate((*bounds, half)):
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)[lo:hi]
        ang = positions3[i].astype(jnp.float32)[..., None] * freqs
        cos_parts.append(jnp.cos(ang))
        sin_parts.append(jnp.sin(ang))
        lo = hi
    cos = jnp.concatenate(cos_parts, axis=-1)    # (B, S, half)
    sin = jnp.concatenate(sin_parts, axis=-1)
    return _rotate(x, cos, sin)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------

def repeat_kv(kv: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """(B, S, KV, hd) -> (B, S, KV*n_rep, hd) by head repetition (GQA)."""
    if n_rep == 1:
        return kv
    b, s, h, d = kv.shape
    return jnp.broadcast_to(kv[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def _mask_bias(sq: int, skv: int, q_offset, *, causal: bool, window) -> jnp.ndarray:
    """Additive f32 mask bias (sq, skv).  q_offset: absolute position of query
    row 0 relative to kv col 0.  ``window`` may be a Python int or a traced
    scalar (per-layer local/global patterns scan it alongside the weights);
    window <= 0 means full attention."""
    qpos = q_offset + jnp.arange(sq)[:, None]
    kpos = jnp.arange(skv)[None, :]
    ok = jnp.ones((sq, skv), jnp.bool_)
    if causal:
        ok &= kpos <= qpos
    w = jnp.asarray(window, jnp.int32)
    ok &= (w <= 0) | (kpos > qpos - w)
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


def attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    *, causal: bool = True, window=0, q_chunk: int = 0,
    softmax_scale: float | None = None, unroll: bool = False,
) -> jnp.ndarray:
    """Multi-head attention over full sequences (train / prefill).

    q: (B, Sq, H, hd); k: (B, Skv, H, hd) (kv already GQA-repeated); v:
    (B, Skv, H, dv), whose head may be narrower than q's (latent attention).
    ``q_chunk`` > 0 bounds memory by scanning over query blocks (the flash-
    attention access pattern expressed in pure JAX; the materialized scores
    are (B, H, q_chunk, Skv) per step instead of (B, H, Sq, Skv)).
    """
    B, Sq, H, hd = q.shape
    Skv, dv = k.shape[1], v.shape[-1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(hd)

    def blk(qb: jnp.ndarray, off) -> jnp.ndarray:
        # bf16 operands, f32 accumulation (preferred_element_type): casting
        # k/v to f32 instead would make XLA hoist a full-stack f32 copy of
        # the weights/caches out of the layer scan.
        s = jnp.einsum("bqhd,bkhd->bhqk", qb * jnp.asarray(scale, qb.dtype), k,
                       preferred_element_type=jnp.float32)
        s = s + _mask_bias(qb.shape[1], Skv, off, causal=causal, window=window)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32).astype(q.dtype)

    if not q_chunk or Sq <= q_chunk:
        return blk(q, 0)

    # pad ragged sequence lengths (e.g. a vision prefix) to a chunk multiple
    # rather than falling back to the materialized (Sq, Skv) score matrix.
    pad = (-Sq) % q_chunk
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else q
    Sq_p = Sq + pad
    nq = Sq_p // q_chunk
    qs = qp.reshape(B, nq, q_chunk, H, hd).transpose(1, 0, 2, 3, 4)

    def body(_, qi_i):
        qi, i = qi_i
        return None, blk(qi, i * q_chunk)

    _, out = jax.lax.scan(body, None, (qs, jnp.arange(nq)),
                          unroll=nq if unroll else 1)
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, Sq_p, H, dv)
    return out[:, :Sq] if pad else out


def decode_attention(
    q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
    length: jnp.ndarray, *, window=0, softmax_scale: float | None = None,
) -> jnp.ndarray:
    """Single-token attention against a KV cache.

    q: (B, 1, H, hd); caches: (B, S, H, hd); length: () or (B,) valid length.
    Written so that when the cache's S axis is sharded, XLA's partial-softmax
    reductions realize the flash-decoding LSE merge across shards.
    """
    B, S, H, hd = k_cache.shape
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q * jnp.asarray(scale, q.dtype), k_cache,
                   preferred_element_type=jnp.float32)    # (B, H, 1, S)
    kpos = jnp.arange(S)[None, None, None, :]
    lb = jnp.asarray(length).reshape(-1, 1, 1, 1)
    ok = kpos < lb
    w = jnp.asarray(window, jnp.int32)
    ok &= (w <= 0) | (kpos >= lb - w)
    s = jnp.where(ok, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v_cache.dtype), v_cache,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def attention_gqa(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    *, causal: bool = True, window=0, q_chunk: int = 0,
    softmax_scale: float | None = None, unroll: bool = False,
) -> jnp.ndarray:
    """Grouped-query attention without materializing repeated K/V.

    q: (B, Sq, H, hd); k, v: (B, Skv, KV, hd) with H = KV * G.  The repeat
    in vanilla ``attention(repeat_kv(k, G), ...)`` writes/reads a G-times
    larger K/V to HBM; here the einsum contracts the grouped layout
    directly (SPerf optimization; flag ArchConfig.gqa_native)."""
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, Sq, KV, G, hd)

    def blk(qb: jnp.ndarray, off) -> jnp.ndarray:
        s = jnp.einsum("bqkgd,bskd->bkgqs", qb * jnp.asarray(scale, qb.dtype),
                       k, preferred_element_type=jnp.float32)
        s = s + _mask_bias(qb.shape[1], Skv, off, causal=causal, window=window)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return o.astype(q.dtype)

    if not q_chunk or Sq <= q_chunk:
        return blk(qg, 0).reshape(B, Sq, H, hd)
    pad = (-Sq) % q_chunk
    qp = jnp.pad(qg, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0))) if pad else qg
    Sq_p = Sq + pad
    nq = Sq_p // q_chunk
    qs = qp.reshape(B, nq, q_chunk, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)

    def body(_, qi_i):
        qi, i = qi_i
        return None, blk(qi, i * q_chunk)

    _, out = jax.lax.scan(body, None, (qs, jnp.arange(nq)),
                          unroll=nq if unroll else 1)
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq_p, H, hd)
    return out[:, :Sq] if pad else out


def decode_attention_gqa(
    q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
    length: jnp.ndarray, *, window=0, softmax_scale: float | None = None,
) -> jnp.ndarray:
    """Single-token GQA attention against an un-repeated (B, S, KV, hd)
    cache."""
    B, S, KV, hd = k_cache.shape
    H = q.shape[2]
    G = H // KV
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, 1, KV, G, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg * jnp.asarray(scale, q.dtype),
                   k_cache, preferred_element_type=jnp.float32)
    kpos = jnp.arange(S)[None, None, None, None, :]
    lb = jnp.asarray(length).reshape(-1, 1, 1, 1, 1)
    ok = kpos < lb
    w = jnp.asarray(window, jnp.int32)
    ok &= (w <= 0) | (kpos >= lb - w)
    s = jnp.where(ok, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, H, hd).astype(q.dtype)


class KVCache(NamedTuple):
    k: jnp.ndarray        # (B, S, KV, hd)
    v: jnp.ndarray        # (B, S, KV, hd)
    length: jnp.ndarray   # () int32 -- tokens already in cache


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------

def swiglu(x: jnp.ndarray, w_gate: jnp.ndarray, w_in: jnp.ndarray, w_out: jnp.ndarray) -> jnp.ndarray:
    h = jax.nn.silu(x @ w_gate) * (x @ w_in)
    return h @ w_out


def gelu_mlp(x: jnp.ndarray, w_in: jnp.ndarray, b_in: jnp.ndarray,
             w_out: jnp.ndarray, b_out: jnp.ndarray) -> jnp.ndarray:
    h = jax.nn.gelu((x @ w_in + b_in).astype(jnp.float32), approximate=True).astype(x.dtype)
    return h @ w_out + b_out
