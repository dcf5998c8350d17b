"""Model FLOPs of local training for the ``moe`` family, from the shapes.

A multiply-add counts 2.  Per sequence of ``S`` tokens and per layer: the
q, k, v and o projections; causal attention (scores and values over the
``S (S + 1) / 2`` visible pairs); the router; and the ``experts_per_tok``
experts each token is routed to (gate, in, out), not the capacity the
program provisions.  Then the head over the vocabulary.  Training counts
the forward three times (forward, and two products per weight in the
backward); recomputation is not counted, and neither is the codec.
"""

from __future__ import annotations


def forward_per_sequence(cfg: dict, S: int) -> int:
    D, H, KV = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    F, E, K, V = cfg["d_ff"], cfg["n_experts"], cfg["experts_per_tok"], cfg["vocab"]
    hd = D // H
    proj = 2 * S * D * (H * hd + 2 * KV * hd) + 2 * S * H * hd * D
    attn = 2 * (2 * H * hd) * (S * (S + 1) // 2)
    router = 2 * S * D * E
    experts = S * K * 3 * (2 * D * F)
    return cfg["n_layers"] * (proj + attn + router + experts) + 2 * S * D * V


def train_per_round(cfg: dict, traffic: dict, n_sel: int) -> int:
    """FLOPs of one round's local training: every selected client takes
    ``local_steps`` steps on ``batch`` sequences."""
    seqs = n_sel * traffic["local_steps"] * traffic["batch"]
    return 3 * seqs * forward_per_sequence(cfg, traffic["seq"])
