"""Plain reference of the decoder-only mixture-of-experts transformer
(configs with ``"family": "moe"``): weights from the seed, the training
loss, and the held-out loss, in straightforward ``jax.numpy``.

What it follows (the configuration file states each value):

* pre-norm blocks: RMSNorm with a ``(1 + w)`` gain, grouped-query causal
  attention with rotary positions (rotate-half on contiguous halves), then a
  top-``experts_per_tok`` routed SwiGLU expert layer, each added to the
  residual; a final RMSNorm and an untied head;
* routing: an f32 softmax router, the top-k gates renormalised to sum to 1,
  and a per-expert capacity ``ceil(T * k * capacity_factor / E)`` over a
  token group of ``T = min(moe_group, tokens)``: choices claim slots in
  choice-major order, and a choice past its expert's capacity is dropped;
* numerics: weights and activations in the configuration's ``dtype``
  (bfloat16), every product of two of them accumulated in f32 and rounded
  to the dtype, norms, softmax and the loss in f32, logits rounded to the
  dtype before the loss.

Unlike the program it computes every expert on every token and selects the
kept ones, attends over the whole sequence at once, and takes the loss
over the whole sequence: the same function, written without capacity
buffers, query chunks or rematerialisation.

``cd`` is the dtype the operands of those products are rounded to before
they meet: the configuration's dtype for the reference, a narrower one for
the precision control (``fedbench.reference``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def init(cfg: dict, key):
    """The program's initial weights, drawn by the same rule from the seed's
    key (the same splits, shapes, dtypes and scales)."""
    dt = jnp.dtype(cfg["dtype"])
    D, F, H, KV = cfg["d_model"], cfg["d_ff"], cfg["n_heads"], cfg["n_kv_heads"]
    L, V, E = cfg["n_layers"], cfg["vocab"], cfg["n_experts"]
    hd = D // H
    kE, kL, kH = jax.random.split(key, 3)
    ks = jax.random.split(kL, 8)
    km = jax.random.split(ks[4], 4)
    s, sf = 1.0 / math.sqrt(D), 1.0 / math.sqrt(F)
    nrm = jax.random.normal
    layers = {
        "ln_attn": jnp.zeros((L, D), dt),
        "ln_mlp": jnp.zeros((L, D), dt),
        "attn_wq": nrm(ks[0], (L, D, H * hd), dt) * s,
        "attn_wk": nrm(ks[1], (L, D, KV * hd), dt) * s,
        "attn_wv": nrm(ks[2], (L, D, KV * hd), dt) * s,
        "attn_wo": nrm(ks[3], (L, H * hd, D), dt) * (1.0 / math.sqrt(H * hd)),
        "router": nrm(km[0], (L, D, E), F32) * s,
        "moe_wgate": nrm(km[1], (L, E, D, F), dt) * s,
        "moe_win": nrm(km[2], (L, E, D, F), dt) * s,
        "moe_wout": nrm(km[3], (L, E, F, D), dt) * sf,
    }
    return {"embed": nrm(kE, (V, D), dt) * 0.02, "layers": layers,
            "ln_f": jnp.zeros((D,), dt),
            "head": nrm(kH, (D, V), dt) / math.sqrt(D)}


def _ein(spec, a, b, cd):
    """Operands rounded to ``cd``, products and sums in f32 (exact products:
    a product of two bf16 or narrower values fits f32)."""
    return jnp.einsum(spec, a.astype(cd).astype(F32), b.astype(cd).astype(F32),
                      precision="highest")


def _rms(x, w, eps):
    x32 = x.astype(F32)
    r = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * r * (1.0 + w.astype(F32))).astype(x.dtype)


def _rope(x, theta):
    B, S, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs          # (S, half)
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def _attention(cfg, h, w, cd):
    B, S, D = h.shape
    H, KV = cfg["n_heads"], cfg["n_kv_heads"]
    hd = D // H
    dt = h.dtype
    q = _ein("bsd,de->bse", h, w["attn_wq"], cd).astype(dt).reshape(B, S, H, hd)
    k = _ein("bsd,de->bse", h, w["attn_wk"], cd).astype(dt).reshape(B, S, KV, hd)
    v = _ein("bsd,de->bse", h, w["attn_wv"], cd).astype(dt).reshape(B, S, KV, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    head_kv = jnp.arange(H) // (H // KV)                      # GQA sharing
    k, v = k[:, :, head_kv], v[:, :, head_kv]
    scale = jnp.asarray(1.0 / math.sqrt(hd), dt)
    s = _ein("bqhd,bkhd->bhqk", q * scale, k, cd)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = s + jnp.where(causal, 0.0, -1e30).astype(F32)
    p = jax.nn.softmax(s, axis=-1)
    o = _ein("bhqk,bkhd->bqhd", p.astype(dt), v, cd).astype(dt)
    return _ein("bse,ed->bsd", o.reshape(B, S, H * hd), w["attn_wo"],
                cd).astype(dt)


def _experts_one_group(cfg, x, w, cd):
    """x: (T, D) one token group -> (T, D)."""
    T, _ = x.shape
    E, K = cfg["n_experts"], cfg["experts_per_tok"]
    C = max(1, int(math.ceil(T * K * cfg["capacity_factor"] / E)))
    dt = x.dtype
    probs = jax.nn.softmax(
        jnp.dot(x.astype(F32), w["router"], precision="highest"), axis=-1)
    gate, idx = jax.lax.top_k(probs, K)                       # (T, K)
    gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)          # (T, K, E)
    # slots are claimed choice by choice, token by token within a choice
    order = onehot.transpose(1, 0, 2).reshape(K * T, E)
    before = (jnp.cumsum(order, axis=0) - order).reshape(K, T, E)
    pos = jnp.sum(before.transpose(1, 0, 2) * onehot, -1)     # (T, K)
    kept = pos < C
    weight = jnp.where(kept, gate.astype(dt), jnp.zeros((), dt))
    per_expert = jnp.einsum("tke,tk->te", onehot.astype(dt), weight)
    hg = _ein("td,edf->tef", x, w["moe_wgate"], cd).astype(dt)
    hi = _ein("td,edf->tef", x, w["moe_win"], cd).astype(dt)
    y = _ein("tef,efd->ted", jax.nn.silu(hg) * hi, w["moe_wout"], cd).astype(dt)
    return _ein("te,ted->td", per_expert, y, cd).astype(dt)


def _experts(cfg, h, w, cd):
    B, S, D = h.shape
    T = B * S
    g = min(cfg["moe_group"], T)
    while T % g:
        g -= 1
    xg = h.reshape(T // g, g, D)
    return jnp.stack([_experts_one_group(cfg, xg[i], w, cd)
                      for i in range(T // g)]).reshape(B, S, D)


def hidden(cfg: dict, params, tokens, cd):
    dt = jnp.dtype(cfg["dtype"])
    x = params["embed"][tokens].astype(dt)
    eps = cfg["norm_eps"]
    for i in range(cfg["n_layers"]):
        w = jax.tree.map(lambda a: a[i], params["layers"])
        x = x + _attention(cfg, _rms(x, w["ln_attn"], eps), w, cd)
        x = x + _experts(cfg, _rms(x, w["ln_mlp"], eps), w, cd)
    return _rms(x, params["ln_f"], eps)


def _logits(cfg, params, batch, cd):
    h = hidden(cfg, params, batch["tokens"], cd)
    dt = jnp.dtype(cfg["dtype"])
    return _ein("bsd,dv->bsv", h, params["head"], cd).astype(dt).astype(F32)


def _token_ce(logits, labels):
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return logz - gold


def loss(cfg: dict, params, batch, cd):
    """Training loss: mean next-token cross-entropy of one batch."""
    return jnp.mean(_token_ce(_logits(cfg, params, batch, cd), batch["labels"]))


def eval_loss(cfg: dict, params, batch, cd):
    """Held-out loss of one batch (the program's eval: the same mean)."""
    return loss(cfg, params, batch, cd)
