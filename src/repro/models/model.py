"""Family dispatcher: one API over all assigned architectures.

  init_params(cfg, key)                       -> params pytree
  forward(cfg, params, batch)                 -> logits (B, S, V)
  loss_fn(cfg, params, batch)                 -> scalar CE loss
  init_cache(cfg, batch, max_len, length)     -> cache pytree
  decode_step(cfg, params, cache, tokens)     -> (logits, cache)
  param_group_shapes(cfg)                     -> compression-policy input
  extra_inputs(cfg, B, S)                     -> modality stubs (audio/vision)

``batch`` is a dict: {"tokens", "labels"} plus optional "audio_frames"
(whisper stub) / "vision_embeds" (qwen2-vl stub) / "positions".
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import encdec, rglru, rwkv6, transformer
from .config import ArchConfig

Params = Dict[str, Any]

__all__ = [
    "init_params", "forward", "loss_fn", "init_cache", "decode_step",
    "param_group_shapes", "extra_inputs", "family_module", "count_params",
]

_TRANSFORMER_FAMILIES = ("dense", "moe", "vlm", "deepseek_v2")


def family_module(cfg: ArchConfig):
    if cfg.family in _TRANSFORMER_FAMILIES:
        return transformer
    if cfg.family == "ssm":
        return rwkv6
    if cfg.family == "hybrid":
        return rglru
    if cfg.family == "encdec":
        return encdec
    raise ValueError(f"unknown family {cfg.family!r}")


def init_params(cfg: ArchConfig, key: jax.Array) -> Params:
    return family_module(cfg).init_params(cfg, key)


def forward(cfg: ArchConfig, params: Params, batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    mod = family_module(cfg)
    kwargs = {}
    if cfg.family == "vlm" and "vision_embeds" in batch:
        kwargs["vision_embeds"] = batch["vision_embeds"]
        if "positions" in batch:
            kwargs["positions"] = batch["positions"]
    if cfg.family == "encdec" and "audio_frames" in batch:
        kwargs["audio_frames"] = batch["audio_frames"]
    return mod.forward(cfg, params, batch["tokens"], **kwargs)


def forward_hidden(cfg: ArchConfig, params: Params, batch: Dict[str, jnp.ndarray]):
    """(hidden (B, S_total, D), head (D, V)) without materializing logits."""
    mod = family_module(cfg)
    kwargs = {}
    if cfg.family == "vlm" and "vision_embeds" in batch:
        kwargs["vision_embeds"] = batch["vision_embeds"]
        if "positions" in batch:
            kwargs["positions"] = batch["positions"]
    if cfg.family == "encdec" and "audio_frames" in batch:
        kwargs["audio_frames"] = batch["audio_frames"]
    return mod.forward_hidden(cfg, params, batch["tokens"], **kwargs)


def loss_fn(cfg: ArchConfig, params: Params, batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """Next-token cross-entropy, mean over tokens, f32.

    The vocabulary projection + CE are evaluated in sequence chunks of
    ``cfg.ce_chunk`` under jax.checkpoint, so the live logits tensor is
    (B, ce_chunk, V) instead of (B, S, V) -- with V up to 262k this is the
    difference between fitting v5e HBM and a 10x overshoot."""
    hidden, head = forward_hidden(cfg, params, batch)
    labels = batch["labels"]
    # vlm prefix tokens carry no labels: align to the trailing label length
    if hidden.shape[1] != labels.shape[1]:
        hidden = hidden[:, -labels.shape[1]:, :]
    B, S, D = hidden.shape
    cs = min(cfg.ce_chunk, S)
    while S % cs:
        cs -= 1
    nc = S // cs

    V = head.shape[-1]
    # padded-vocab columns (pad_vocab_multiple) must not leak probability
    pad_bias = (
        jnp.where(jnp.arange(V) < cfg.vocab, 0.0, -1e30).astype(jnp.float32)
        if V != cfg.vocab else None
    )

    def chunk_ce(h_c, y_c):
        logits = (h_c @ head).astype(jnp.float32)          # (B, cs, V)
        if pad_bias is not None:
            logits = logits + pad_bias
        logz = jax.nn.logsumexp(logits, axis=-1)
        # one-hot contraction instead of take_along_axis: a gather over the
        # vocab axis would force GSPMD to all-gather the sharded logits.
        onehot = jax.nn.one_hot(y_c, V, dtype=logits.dtype)
        gold = jnp.sum(logits * onehot, axis=-1)
        return jnp.sum(logz - gold)

    if nc == 1:
        return chunk_ce(hidden, labels) / (B * S)

    hs = hidden.reshape(B, nc, cs, D).swapaxes(0, 1)       # (nc, B, cs, D)
    ys = labels.reshape(B, nc, cs).swapaxes(0, 1)

    def body(tot, xs):
        h_c, y_c = xs
        return tot + chunk_ce(h_c, y_c), None

    body_ck = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    total, _ = jax.lax.scan(body_ck, jnp.zeros((), jnp.float32), (hs, ys),
                            unroll=nc if cfg.attn_unroll else 1)
    return total / (B * S)


def init_cache(cfg: ArchConfig, batch: int, max_len: int, length=0, **kw):
    return family_module(cfg).init_cache(cfg, batch, max_len, length, **kw)


def decode_step(cfg: ArchConfig, params: Params, cache, tokens: jnp.ndarray):
    return family_module(cfg).decode_step(cfg, params, cache, tokens)


def param_group_shapes(cfg: ArchConfig):
    return family_module(cfg).param_group_shapes(cfg)


def extra_inputs(cfg: ArchConfig, batch: int, seq: int, dtype=None) -> Dict[str, jnp.ndarray]:
    """Modality-frontend stubs (the one allowed stub: precomputed embeddings)."""
    dt = jnp.dtype(dtype or cfg.dtype)
    out: Dict[str, jnp.ndarray] = {}
    if cfg.family == "encdec":
        out["audio_frames"] = jnp.zeros((batch, cfg.encoder_seq, cfg.d_model), dt)
    if cfg.family == "vlm" and cfg.vision_tokens:
        out["vision_embeds"] = jnp.zeros((batch, cfg.vision_tokens, cfg.d_model), dt)
    return out


def count_params(params: Params) -> int:
    return sum(int(jnp.size(x)) for x in jax.tree.leaves(params))
