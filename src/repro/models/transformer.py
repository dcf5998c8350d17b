"""Decoder-only transformer stack (dense + MoE families).

Covers llama3-8b, tinyllama-1.1b, yi-34b (plain GQA), gemma3-1b (5:1
local:global sliding-window pattern), qwen2-vl-72b (M-RoPE + vision-prefix
stub), granite-moe / dbrx (MoE FFN via moe.py).

Layer parameters are *stacked* along a leading L axis and the stack is
iterated with ``lax.scan`` so the HLO stays compact for 40..80-layer configs
(see DESIGN.md Sec. 6 on how the roofline accounts for scan trip counts).

Per-layer heterogeneity (gemma3's local/global pattern) is carried as a
per-layer ``window`` array scanned alongside the weights -- the mask is
computed with dynamic window arithmetic so one traced body serves both kinds.

deepseek-v2 (``kv_lora_rank`` > 0): multi-head latent attention (``mla.py``)
in every layer; the first ``first_k_dense_replace`` layers have a dense
SwiGLU (``dense_mlp_*`` leaves, stacked over those layers) and run before
the scan, which covers the expert layers (``router``/``moe_*``/``shared_*``
leaves, stacked over them); the attention and norm leaves stack over all
layers.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ArchConfig
from .layers import (
    KVCache,
    apply_mrope,
    apply_rope,
    attention,
    attention_gqa,
    decode_attention,
    decode_attention_gqa,
    repeat_kv,
    rms_norm,
    swiglu,
)
from . import mla
from .moe import init_moe_ffn, init_shared_experts, moe_ffn

Params = Dict[str, Any]

__all__ = [
    "init_params", "forward", "forward_hidden", "init_cache", "decode_step",
    "layer_fwd", "param_group_shapes",
]


def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


def _init_dense_layer(cfg: ArchConfig, key: jax.Array, L: int) -> Params:
    D, F, H, KV, hd = cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 8)
    dt = _dtype(cfg)
    s = 1.0 / math.sqrt(D)
    sf = 1.0 / math.sqrt(F)
    p = {
        "ln_attn": jnp.zeros((L, D), dt),
        "ln_mlp": jnp.zeros((L, D), dt),
        "attn_wq": jax.random.normal(ks[0], (L, D, H * hd), dt) * s,
        "attn_wk": jax.random.normal(ks[1], (L, D, KV * hd), dt) * s,
        "attn_wv": jax.random.normal(ks[2], (L, D, KV * hd), dt) * s,
        "attn_wo": jax.random.normal(ks[3], (L, H * hd, D), dt) * (1.0 / math.sqrt(H * hd)),
    }
    if cfg.n_experts:
        p.update(init_moe_ffn(cfg, ks[4], L))
    else:
        p.update({
            "mlp_wgate": jax.random.normal(ks[5], (L, D, F), dt) * s,
            "mlp_win": jax.random.normal(ks[6], (L, D, F), dt) * s,
            "mlp_wout": jax.random.normal(ks[7], (L, F, D), dt) * sf,
        })
    return p


def _init_latent_layers(cfg: ArchConfig, key: jax.Array) -> Params:
    """deepseek-v2's layer leaves (module docstring)."""
    L, Ld = cfg.n_layers, cfg.first_k_dense_replace
    D, Fd = cfg.d_model, cfg.dense_d_ff
    dt = _dtype(cfg)
    ka, kd, km, ks = jax.random.split(key, 4)
    p = {"ln_attn": jnp.zeros((L, D), dt), "ln_mlp": jnp.zeros((L, D), dt)}
    p.update(mla.init_mla(cfg, ka, L))
    if Ld:
        kg = jax.random.split(kd, 3)
        s, sf = 1.0 / math.sqrt(D), 1.0 / math.sqrt(Fd)
        p.update({
            "dense_mlp_wgate": jax.random.normal(kg[0], (Ld, D, Fd), dt) * s,
            "dense_mlp_win": jax.random.normal(kg[1], (Ld, D, Fd), dt) * s,
            "dense_mlp_wout": jax.random.normal(kg[2], (Ld, Fd, D), dt) * sf,
        })
    p.update(init_moe_ffn(cfg, km, L - Ld))
    if cfg.n_shared_experts:
        p.update(init_shared_experts(cfg, ks, L - Ld))
    return p


def _expert_leaf(name: str) -> bool:
    return name == "router" or name.startswith(("moe_", "shared_"))


def _split_dense(cfg: ArchConfig, layers: Params):
    """([leaves of dense layer i], leaves of the expert-layer stack): the
    leaves stacked over every layer are indexed and sliced."""
    Ld = cfg.first_k_dense_replace
    dense = [{k: v[i] for k, v in layers.items() if not _expert_leaf(k)}
             for i in range(Ld)]
    stack = {k: (v if _expert_leaf(k) else v[Ld:])
             for k, v in layers.items() if not k.startswith("dense_")}
    return dense, stack


def padded_vocab(cfg: ArchConfig) -> int:
    """Megatron-style vocab padding so embed/head shard over "model"
    (SPerf switch; 0 = off)."""
    m = cfg.pad_vocab_multiple
    if not m:
        return cfg.vocab
    return ((cfg.vocab + m - 1) // m) * m


def init_params(cfg: ArchConfig, key: jax.Array) -> Params:
    kE, kL, kH = jax.random.split(key, 3)
    dt = _dtype(cfg)
    D, V, L = cfg.d_model, padded_vocab(cfg), cfg.n_layers
    params: Params = {
        "embed": jax.random.normal(kE, (V, D), dt) * 0.02,
        "layers": (_init_latent_layers(cfg, kL) if cfg.kv_lora_rank
                   else _init_dense_layer(cfg, kL, L)),
        "ln_f": jnp.zeros((D,), dt),
    }
    if not cfg.tie_embeddings:
        params["head"] = jax.random.normal(kH, (D, V), dt) / math.sqrt(D)
    return params


def _layer_windows(cfg: ArchConfig) -> jnp.ndarray:
    """Per-layer sliding window (0 = full attention), from the pattern."""
    kinds = cfg.layer_kinds()
    return jnp.asarray(
        [cfg.sliding_window if k == "local" else 0 for k in kinds], jnp.int32
    )


def _positions_for(cfg: ArchConfig, tokens: jnp.ndarray, offset=0) -> jnp.ndarray:
    B, S = tokens.shape[0], tokens.shape[1]
    pos = offset + jnp.arange(S, dtype=jnp.int32)[None, :]
    pos = jnp.broadcast_to(pos, (B, S))
    if cfg.pos_type == "mrope":
        return jnp.broadcast_to(pos[None], (3, B, S))   # text-only default
    return pos


def layer_fwd(
    cfg: ArchConfig,
    x: jnp.ndarray,                 # (B, S, D)
    w: Params,                      # one layer's params (no L axis)
    positions: jnp.ndarray,         # (B, S) or (3, B, S) for mrope
    window: jnp.ndarray,            # () int32, 0 = full
    q_chunk: int = 0,
) -> jnp.ndarray:
    """One transformer block (pre-norm attention + FFN/MoE)."""
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd

    h = rms_norm(x, w["ln_attn"], cfg.norm_eps)
    if cfg.kv_lora_rank:
        x = x + mla.mla_attention(cfg, h, w, positions, window, q_chunk)
        return x + _ffn(cfg, rms_norm(x, w["ln_mlp"], cfg.norm_eps), w)
    q = (h @ w["attn_wq"]).reshape(B, S, H, hd)
    k = (h @ w["attn_wk"]).reshape(B, S, KV, hd)
    v = (h @ w["attn_wv"]).reshape(B, S, KV, hd)
    if cfg.pos_type == "mrope":
        q = apply_mrope(q, positions, cfg.rope_theta)
        k = apply_mrope(k, positions, cfg.rope_theta)
    elif cfg.pos_type == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if cfg.gqa_native and KV != H:
        o = attention_gqa(q, k, v, causal=True, window=window,
                          q_chunk=q_chunk, unroll=cfg.attn_unroll)
    else:
        o = attention(q, repeat_kv(k, H // KV), repeat_kv(v, H // KV),
                      causal=True, window=window, q_chunk=q_chunk,
                      unroll=cfg.attn_unroll)
    x = x + o.reshape(B, S, H * hd) @ w["attn_wo"]

    h = rms_norm(x, w["ln_mlp"], cfg.norm_eps)
    return x + _ffn(cfg, h, w)


def _ffn(cfg: ArchConfig, h: jnp.ndarray, w: Params) -> jnp.ndarray:
    if "dense_mlp_wgate" in w:
        return swiglu(h, w["dense_mlp_wgate"], w["dense_mlp_win"],
                      w["dense_mlp_wout"])
    if cfg.n_experts:
        return moe_ffn(cfg, h, w)
    return swiglu(h, w["mlp_wgate"], w["mlp_win"], w["mlp_wout"])


def forward_hidden(
    cfg: ArchConfig,
    params: Params,
    tokens: jnp.ndarray,                       # (B, S) int32
    positions: Optional[jnp.ndarray] = None,
    vision_embeds: Optional[jnp.ndarray] = None,  # (B, P, D) stub prefix
    q_chunk: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Forward up to the final norm: (hidden (B, S_total, D), head (D, V))."""
    dt = _dtype(cfg)
    x = params["embed"][tokens].astype(dt)
    if cfg.scale_embed:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dt)
    if vision_embeds is not None:
        x = jnp.concatenate([vision_embeds.astype(dt), x], axis=1)
    B, S, D = x.shape
    if positions is None:
        positions = _positions_for(cfg, jnp.zeros((B, S)))
    qc = cfg.attn_chunk if q_chunk is None else q_chunk
    windows = _layer_windows(cfg)

    body_fn = lambda xc, lw: (
        layer_fwd(cfg, xc, lw[0], positions, lw[1], q_chunk=qc), None
    )
    if cfg.remat:
        body_fn = jax.checkpoint(
            body_fn, policy=jax.checkpoint_policies.nothing_saveable
        )
    layers = params["layers"]
    if cfg.first_k_dense_replace:
        dense, layers = _split_dense(cfg, layers)
        for i, w in enumerate(dense):
            x, _ = body_fn(x, (w, windows[i]))
        windows = windows[len(dense):]
    x, _ = jax.lax.scan(body_fn, x, (layers, windows),
                       unroll=cfg.scan_unroll)
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    return x, head


def forward(cfg: ArchConfig, params: Params, tokens: jnp.ndarray, **kw) -> jnp.ndarray:
    """Full logits (B, S_total, V) -- smoke/eval use; the training loss and
    the prefill step use forward_hidden to avoid materializing (B, S, V)."""
    x, head = forward_hidden(cfg, params, tokens, **kw)
    return (x @ head).astype(jnp.float32)[..., : cfg.vocab]


# --------------------------------------------------------------------------
# decode path
# --------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, max_len: int, length=0) -> KVCache:
    """Stacked (L, B, S, KV, hd) KV cache; ``length`` marks pre-filled tokens
    (for dry-runs the cache content is abstract)."""
    dt = _dtype(cfg)
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
    if cfg.kv_lora_rank:
        # expanded per-head keys and values (the latent-absorbed cache is a
        # serving optimisation no training path runs)
        H = cfg.n_heads
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        return KVCache(
            k=jnp.zeros((L, batch, max_len, H, qk), dt),
            v=jnp.zeros((L, batch, max_len, H, cfg.v_head_dim), dt),
            length=jnp.asarray(length, jnp.int32),
        )
    return KVCache(
        k=jnp.zeros((L, batch, max_len, KV, hd), dt),
        v=jnp.zeros((L, batch, max_len, KV, hd), dt),
        length=jnp.asarray(length, jnp.int32),
    )


def decode_step(
    cfg: ArchConfig,
    params: Params,
    cache: KVCache,
    tokens: jnp.ndarray,           # (B, 1) int32
) -> Tuple[jnp.ndarray, KVCache]:
    """One new token against the KV cache; returns (logits (B, 1, V), cache)."""
    dt = _dtype(cfg)
    B = tokens.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    x = params["embed"][tokens].astype(dt)
    if cfg.scale_embed:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dt)
    pos = jnp.broadcast_to(cache.length[None, None], (B, 1))
    positions = jnp.broadcast_to(pos[None], (3, B, 1)) if cfg.pos_type == "mrope" else pos
    windows = _layer_windows(cfg)

    def body(carry, lw):
        x, = carry
        w, window, kc, vc = lw
        h = rms_norm(x, w["ln_attn"], cfg.norm_eps)
        if cfg.kv_lora_rank:
            with jax.named_scope(mla.MLA_SCOPE):
                q, k, v = mla.project(cfg, h, w, pos)
                kc = jax.lax.dynamic_update_slice(kc, k, (0, cache.length, 0, 0))
                vc = jax.lax.dynamic_update_slice(vc, v, (0, cache.length, 0, 0))
                o = decode_attention(q, kc, vc, cache.length + 1, window=window,
                                     softmax_scale=mla.softmax_scale(cfg))
                x = x + o.reshape(B, 1, -1) @ w["attn_wo"]
            h2 = rms_norm(x, w["ln_mlp"], cfg.norm_eps)
            return (x + _ffn(cfg, h2, w),), (kc, vc)
        q = (h @ w["attn_wq"]).reshape(B, 1, H, hd)
        k = (h @ w["attn_wk"]).reshape(B, 1, KV, hd)
        v = (h @ w["attn_wv"]).reshape(B, 1, KV, hd)
        if cfg.pos_type == "mrope":
            q = apply_mrope(q, positions, cfg.rope_theta)
            k = apply_mrope(k, positions, cfg.rope_theta)
        elif cfg.pos_type == "rope":
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        kc = jax.lax.dynamic_update_slice(kc, k, (0, cache.length, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v, (0, cache.length, 0, 0))
        if cfg.gqa_native and KV != H:
            o = decode_attention_gqa(q, kc, vc, cache.length + 1, window=window)
        else:
            o = decode_attention(
                q, repeat_kv(kc, H // KV), repeat_kv(vc, H // KV),
                cache.length + 1, window=window,
            )
        x = x + o.reshape(B, 1, H * hd) @ w["attn_wo"]
        h2 = rms_norm(x, w["ln_mlp"], cfg.norm_eps)
        return (x + _ffn(cfg, h2, w),), (kc, vc)

    layers, ck, cv = params["layers"], cache.k, cache.v
    Ld = cfg.first_k_dense_replace
    dense_kv = []
    if Ld:
        dense, layers = _split_dense(cfg, layers)
        for i, w in enumerate(dense):
            (x,), kv = body((x,), (w, windows[i], ck[i], cv[i]))
            dense_kv.append(kv)
        windows, ck, cv = windows[Ld:], ck[Ld:], cv[Ld:]
    (x,), (k_new, v_new) = jax.lax.scan(body, (x,), (layers, windows, ck, cv))
    if dense_kv:
        k_new = jnp.concatenate([jnp.stack([k for k, _ in dense_kv]), k_new])
        v_new = jnp.concatenate([jnp.stack([v for _, v in dense_kv]), v_new])
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = (x @ head).astype(jnp.float32)[..., : cfg.vocab]
    return logits, KVCache(k=k_new, v=v_new, length=cache.length + 1)


# --------------------------------------------------------------------------
# compression-policy hook
# --------------------------------------------------------------------------

def _latent_group_shapes(cfg: ArchConfig) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    D, F, H, L = cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.n_layers
    Ld, r = cfg.first_k_dense_replace, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    Lm, E, Eh, Fd = L - Ld, cfg.n_experts, cfg.held, cfg.dense_d_ff
    Fs = cfg.n_shared_experts * F
    groups = {
        "layers/ln_attn": ((D,), L),
        "layers/ln_mlp": ((D,), L),
        "layers/attn_wq": ((D, H * (nope + rope)), L),
        "layers/attn_wkv_a": ((D, r + rope), L),
        "layers/attn_kv_norm": ((r,), L),
        "layers/attn_wkv_b": ((r, H * (nope + dv)), L),
        "layers/attn_wo": ((H * dv, D), L),
        "layers/router": ((D, E), Lm),
        "layers/moe_wgate": ((Eh, D, F), Lm),
        "layers/moe_win": ((Eh, D, F), Lm),
        "layers/moe_wout": ((Eh, F, D), Lm),
        "embed": ((padded_vocab(cfg), D), 1),
        "ln_f": ((D,), 1),
    }
    if Ld:
        groups.update({
            "layers/dense_mlp_wgate": ((D, Fd), Ld),
            "layers/dense_mlp_win": ((D, Fd), Ld),
            "layers/dense_mlp_wout": ((Fd, D), Ld),
        })
    if Fs:
        groups.update({
            "layers/shared_wgate": ((D, Fs), Lm),
            "layers/shared_win": ((D, Fs), Lm),
            "layers/shared_wout": ((Fs, D), Lm),
        })
    if not cfg.tie_embeddings:
        groups["head"] = ((D, padded_vocab(cfg)), 1)
    return groups


def param_group_shapes(cfg: ArchConfig) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    """{group: (per-layer shape, stack)} for the GradESTC policy."""
    if cfg.kv_lora_rank:
        return _latent_group_shapes(cfg)
    D, F, H, KV, hd, L = cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.n_layers
    groups = {
        "layers/attn_wq": ((D, H * hd), L),
        "layers/attn_wk": ((D, KV * hd), L),
        "layers/attn_wv": ((D, KV * hd), L),
        "layers/attn_wo": ((H * hd, D), L),
        "layers/ln_attn": ((D,), L),
        "layers/ln_mlp": ((D,), L),
        "embed": ((padded_vocab(cfg), D), 1),
        "ln_f": ((D,), 1),
    }
    if cfg.n_experts:
        E, Eh = cfg.n_experts, cfg.held
        groups.update({
            "layers/moe_wgate": ((Eh, D, F), L),
            "layers/moe_win": ((Eh, D, F), L),
            "layers/moe_wout": ((Eh, F, D), L),
            "layers/router": ((D, E), L),
        })
    else:
        groups.update({
            "layers/mlp_wgate": ((D, F), L),
            "layers/mlp_win": ((D, F), L),
            "layers/mlp_wout": ((F, D), L),
        })
    if not cfg.tie_embeddings:
        groups["head"] = ((D, padded_vocab(cfg)), 1)
    return groups
