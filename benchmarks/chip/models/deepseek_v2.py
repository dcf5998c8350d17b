"""Plain reference of DeepSeek-V2 (configs with ``"family": "deepseek_v2"``):
weights from the seed, the training loss, and the held-out loss, in
straightforward ``jax.numpy``, after arXiv 2405.04434 and the published
``modeling_deepseek.py``.

What it follows (the configuration file states each value):

* pre-norm blocks: RMSNorm with a ``(1 + w)`` gain, multi-head latent
  attention, then a feed-forward layer, each added to the residual; a final
  RMSNorm and an untied head;
* latent attention without query compression: ``q = h W_q`` (heads of
  ``qk_nope_head_dim + qk_rope_head_dim``), ``[c, k_pe] = h W_kva``, ``c``
  RMS-normed, ``[k_nope, v] = c W_kvb``; ``k_pe`` is one rotary key shared by
  every head; YaRN rotary frequencies on the rope dims (rotate-half order);
  causal softmax over ``[q_nope q_pe] . [k_nope k_pe]`` scaled by
  ``(nope + rope)^-1/2 * m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``;
  the value head ``v_head_dim``;
* layers ``0 .. first_k_dense_replace - 1``: a SwiGLU of width
  ``dense_d_ff``; the rest: an f32 softmax router over ``n_experts``, the
  top-``experts_per_tok`` gates renormalised only where ``norm_topk_prob``,
  a per-expert capacity ``ceil(T * k * capacity_factor / n_experts)`` over a
  token group of ``T = min(moe_group, tokens)`` (choices claim slots in
  choice-major order, a choice past its expert's capacity is dropped); the
  experts ``0 .. experts_held - 1`` held here give their part of the result
  (SwiGLU of width ``d_ff``), and the ``n_shared_experts`` shared experts (one
  SwiGLU of width ``n_shared_experts * d_ff``) are added on every token;
* numerics: weights and activations in the configuration's ``dtype``
  (bfloat16), every product of two of them accumulated in f32 and rounded
  to the dtype, norms, rotations, softmax and the loss in f32, logits
  rounded to the dtype before the loss.

Unlike the program it computes every held expert on every token and
selects the kept ones, expands the latent keys and values and attends over
the whole sequence at once, and takes the loss over the whole sequence:
the same function, written without capacity buffers, query chunks or
rematerialisation.

``cd`` is the dtype the operands of those products are rounded to before
they meet: the configuration's dtype for the reference, a narrower one for
the precision control (``fedbench.reference``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def init(cfg: dict, key):
    """The program's initial weights, drawn by the same rule from the seed's
    key (the same splits, shapes, dtypes and scales)."""
    dt = jnp.dtype(cfg["dtype"])
    D, H, V, L = cfg["d_model"], cfg["n_heads"], cfg["vocab"], cfg["n_layers"]
    r, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    rope, dv = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    Ld = cfg["first_k_dense_replace"]
    Lm, Fd, F = L - Ld, cfg["dense_d_ff"], cfg["d_ff"]
    E, Eh = cfg["n_experts"], cfg.get("experts_held") or cfg["n_experts"]
    Fs = cfg["n_shared_experts"] * F
    s = 1.0 / math.sqrt(D)
    nrm = jax.random.normal
    kE, kL, kH = jax.random.split(key, 3)
    ka, kd, km, ks = jax.random.split(kL, 4)
    qa = jax.random.split(ka, 4)
    qd = jax.random.split(kd, 3)
    qm = jax.random.split(km, 4)
    qs = jax.random.split(ks, 3)
    layers = {
        "ln_attn": jnp.zeros((L, D), dt),
        "ln_mlp": jnp.zeros((L, D), dt),
        "attn_wq": nrm(qa[0], (L, D, H * (nope + rope)), dt) * s,
        "attn_wkv_a": nrm(qa[1], (L, D, r + rope), dt) * s,
        "attn_kv_norm": jnp.zeros((L, r), dt),
        "attn_wkv_b": nrm(qa[2], (L, r, H * (nope + dv)), dt) * (1.0 / math.sqrt(r)),
        "attn_wo": nrm(qa[3], (L, H * dv, D), dt) * (1.0 / math.sqrt(H * dv)),
    }
    if Ld:
        layers.update({
            "dense_mlp_wgate": nrm(qd[0], (Ld, D, Fd), dt) * s,
            "dense_mlp_win": nrm(qd[1], (Ld, D, Fd), dt) * s,
            "dense_mlp_wout": nrm(qd[2], (Ld, Fd, D), dt) * (1.0 / math.sqrt(Fd)),
        })
    layers.update({
        "router": nrm(qm[0], (Lm, D, E), F32) * s,
        "moe_wgate": nrm(qm[1], (Lm, Eh, D, F), dt) * s,
        "moe_win": nrm(qm[2], (Lm, Eh, D, F), dt) * s,
        "moe_wout": nrm(qm[3], (Lm, Eh, F, D), dt) * (1.0 / math.sqrt(F)),
    })
    if Fs:
        layers.update({
            "shared_wgate": nrm(qs[0], (Lm, D, Fs), dt) * s,
            "shared_win": nrm(qs[1], (Lm, D, Fs), dt) * s,
            "shared_wout": nrm(qs[2], (Lm, Fs, D), dt) * (1.0 / math.sqrt(Fs)),
        })
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


# ---------------------------------------------------------------------------
# YaRN (DeepseekV2YarnRotaryEmbedding), written out
# ---------------------------------------------------------------------------

def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn(cfg: dict):
    """(inverse frequencies of the rope dims, amplitude of cos and sin,
    softmax scale)."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    factor, orig = cfg["yarn_factor"], cfg["yarn_original_max_position"]
    i = np.arange(0, dim, 2, dtype=np.float64)
    freq_extra = 1.0 / base ** (i / dim)
    if not factor:              # plain RoPE
        return freq_extra.astype(np.float32), 1.0, (cfg["qk_nope_head_dim"] + dim) ** -0.5
    freq_inter = 1.0 / (factor * base ** (i / dim))

    def corr(n_rot):            # dims that turn n_rot times over orig
        return dim * math.log(orig / (n_rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(cfg["yarn_beta_fast"])), 0)
    high = min(math.ceil(corr(cfg["yarn_beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    extra_mask = 1.0 - ramp
    inv_freq = freq_inter * (1.0 - extra_mask) + freq_extra * extra_mask
    amp = (_mscale(factor, cfg["yarn_mscale"])
           / _mscale(factor, cfg["yarn_mscale_all_dim"]))
    m = _mscale(factor, cfg["yarn_mscale_all_dim"])
    scale = (cfg["qk_nope_head_dim"] + dim) ** -0.5 * m * m
    return inv_freq.astype(np.float32), amp, scale


def _rope(x, inv_freq, amp):
    """x: (B, S, h, rope), rotate-half over positions 0 .. S-1."""
    S, half = x.shape[1], x.shape[-1] // 2
    ang = jnp.arange(S, dtype=F32)[:, None] * jnp.asarray(inv_freq)
    c = (jnp.cos(ang) * amp)[None, :, None, :]
    s = (jnp.sin(ang) * amp)[None, :, None, :]
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def _attention(cfg, h, w, cd):
    B, S, D = h.shape
    H, r = cfg["n_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    dt = h.dtype
    inv_freq, amp, scale = yarn(cfg)
    q = _ein("bsd,de->bse", h, w["attn_wq"], cd).astype(dt)
    q = q.reshape(B, S, H, nope + rope)
    ckv = _ein("bsd,de->bse", h, w["attn_wkv_a"], cd).astype(dt)
    c = _rms(ckv[..., :r], w["attn_kv_norm"], cfg["norm_eps"])
    k_pe = _rope(ckv[..., r:].reshape(B, S, 1, rope), inv_freq, amp)
    kv = _ein("bsr,re->bse", c, w["attn_wkv_b"], cd).astype(dt)
    kv = kv.reshape(B, S, H, nope + dv)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv_freq, amp)],
                        axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (B, S, H, rope))], axis=-1)
    v = kv[..., nope:]
    s = _ein("bqhd,bkhd->bhqk", q * jnp.asarray(scale, dt), k, cd)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = s + jnp.where(causal, 0.0, -1e30).astype(F32)
    p = jax.nn.softmax(s, axis=-1)
    o = _ein("bhqk,bkhd->bqhd", p.astype(dt), v, cd).astype(dt)
    return _ein("bse,ed->bsd", o.reshape(B, S, H * dv), w["attn_wo"],
                cd).astype(dt)


def _swiglu(x, wg, wi, wo, cd):
    dt = x.dtype
    hg = _ein("...d,df->...f", x, wg, cd).astype(dt)
    hi = _ein("...d,df->...f", x, wi, cd).astype(dt)
    return _ein("...f,fd->...d", jax.nn.silu(hg) * hi, wo, cd).astype(dt)


def _experts_one_group(cfg, x, w, cd):
    """x: (T, D) one token group -> (T, D), the held experts' part."""
    T, _ = x.shape
    E, K = cfg["n_experts"], cfg["experts_per_tok"]
    Eh = cfg.get("experts_held") or E
    C = max(1, int(math.ceil(T * K * cfg["capacity_factor"] / E)))
    dt = x.dtype
    probs = jax.nn.softmax(
        jnp.dot(x.astype(F32), w["router"], precision="highest"), axis=-1)
    gate, idx = jax.lax.top_k(probs, K)                       # (T, K)
    if cfg.get("norm_topk_prob", True):
        gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)          # (T, K, E)
    # slots are claimed choice by choice, token by token within a choice
    order = onehot.transpose(1, 0, 2).reshape(K * T, E)
    before = (jnp.cumsum(order, axis=0) - order).reshape(K, T, E)
    pos = jnp.sum(before.transpose(1, 0, 2) * onehot, -1)     # (T, K)
    kept = pos < C
    weight = jnp.where(kept, gate.astype(dt), jnp.zeros((), dt))
    per_expert = jnp.einsum("tke,tk->te", onehot.astype(dt), weight)[:, :Eh]
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
    routed = jnp.stack([_experts_one_group(cfg, xg[i], w, cd)
                        for i in range(T // g)]).reshape(B, S, D)
    if not cfg["n_shared_experts"]:
        return routed
    return routed + _swiglu(h, w["shared_wgate"], w["shared_win"],
                            w["shared_wout"], cd)


def hidden(cfg: dict, params, tokens, cd):
    dt = jnp.dtype(cfg["dtype"])
    x = params["embed"][tokens].astype(dt)
    eps, Ld = cfg["norm_eps"], cfg["first_k_dense_replace"]
    lay = params["layers"]
    for i in range(cfg["n_layers"]):
        w = {k: v[i] for k, v in lay.items()
             if k.startswith(("ln_", "attn_"))}
        x = x + _attention(cfg, _rms(x, w["ln_attn"], eps), w, cd)
        h = _rms(x, w["ln_mlp"], eps)
        if i < Ld:
            x = x + _swiglu(h, lay["dense_mlp_wgate"][i],
                            lay["dense_mlp_win"][i],
                            lay["dense_mlp_wout"][i], cd)
        else:
            we = {k: v[i - Ld] for k, v in lay.items()
                  if k == "router" or k.startswith(("moe_", "shared_"))}
            x = x + _experts(cfg, h, we, cd)
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
