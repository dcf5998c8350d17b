"""Model FLOPs of local training for the ``deepseek_v2`` family, from the
shapes.

A multiply-add counts 2.  Per sequence of ``S`` tokens and per layer: the
latent attention's projections (W_q, W_kva, W_kvb, W_o); causal attention
over the ``S (S + 1) / 2`` visible pairs, scores at the q/k head (nope +
rope) and values at the v head; then either the dense SwiGLU (the first
``first_k_dense_replace`` layers) or the router over all ``n_experts``,
``experts_per_tok * experts_held / n_experts`` routed expert passes a token
(the share of the top-k choices that fall on the experts held here, not the
capacity the program provisions) and the shared experts' SwiGLU.  Then the
head over the vocabulary.  Training counts the forward three times
(forward, and two products per weight in the backward); recomputation is
not counted, and neither is the codec.
"""

from __future__ import annotations


def forward_per_sequence(cfg: dict, S: int) -> int:
    D, H, V = cfg["d_model"], cfg["n_heads"], cfg["vocab"]
    r, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    rope, dv = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    L, Ld = cfg["n_layers"], cfg["first_k_dense_replace"]
    F, E, K = cfg["d_ff"], cfg["n_experts"], cfg["experts_per_tok"]
    Eh = cfg.get("experts_held") or E
    Fs = cfg["n_shared_experts"] * F
    proj = 2 * S * (D * H * (nope + rope) + D * (r + rope)
                    + r * H * (nope + dv) + H * dv * D)
    attn = 2 * H * (nope + rope + dv) * (S * (S + 1) // 2)
    dense = 3 * 2 * S * D * cfg["dense_d_ff"]
    router = 2 * S * D * E
    routed = S * K * Eh * 3 * 2 * D * F // E
    shared = 3 * 2 * S * D * Fs
    return (L * (proj + attn) + Ld * dense + (L - Ld) * (router + routed + shared)
            + 2 * S * D * V)


def train_per_round(cfg: dict, traffic: dict, n_sel: int) -> int:
    """FLOPs of one round's local training: every selected client takes
    ``local_steps`` steps on ``batch`` sequences."""
    seqs = n_sel * traffic["local_steps"] * traffic["batch"]
    return 3 * seqs * forward_per_sequence(cfg, traffic["seq"])
