"""deepseek-v2-lite [deepseek_v2] -- latent attention, 2 shared + 64 routed
experts top-6 [hf:deepseek-ai/DeepSeek-V2-Lite].

27L (layer 0 a dense SwiGLU of width 10944, then 26 expert layers)
d_model=2048 16H; MLA without query compression: latent 512, q/k head 128
nope + 64 rope, v head 128, YaRN x40 on the rope dims; 64 routed experts of
width 1408, top-6 with the gates not renormalised, plus 2 shared experts;
vocab=102400, untied head.  Equations: arXiv 2405.04434 and the published
``modeling_deepseek.py``.
"""

from repro.models.config import ArchConfig

SOURCE = "https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json"

#: the source's ``config.json``, the keys that describe the model's shape
PUBLISHED = {
    "attention_bias": False,
    "first_k_dense_replace": 1,
    "hidden_act": "silu",
    "hidden_size": 2048,
    "intermediate_size": 10944,
    "kv_lora_rank": 512,
    "max_position_embeddings": 163840,
    "model_type": "deepseek_v2",
    "moe_intermediate_size": 1408,
    "moe_layer_freq": 1,
    "n_group": 1,
    "n_routed_experts": 64,
    "n_shared_experts": 2,
    "norm_topk_prob": False,
    "num_attention_heads": 16,
    "num_experts_per_tok": 6,
    "num_hidden_layers": 27,
    "num_key_value_heads": 16,
    "q_lora_rank": None,
    "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000,
    "routed_scaling_factor": 1,
    "scoring_func": "softmax",
    "seq_aux": True,
    "tie_word_embeddings": False,
    "topk_group": 1,
    "topk_method": "greedy",
    "v_head_dim": 128,
    "vocab_size": 102400,
}

#: settings of the source the program runs only at these values
_FIXED = {"attention_bias": False, "hidden_act": "silu", "moe_layer_freq": 1,
          "n_group": 1, "q_lora_rank": None, "routed_scaling_factor": 1,
          "scoring_func": "softmax", "topk_group": 1, "topk_method": "greedy"}


def from_published(c: dict, **overrides) -> ArchConfig:
    """The program's configuration of a DeepSeek-V2 ``config.json``.

    ``seq_aux`` (the sequence-wise balance loss) and the position limits the
    cache would need are not read: the federated loss is next-token
    cross-entropy for every family.  A setting in ``_FIXED`` at another
    value is refused."""
    off = {k: c.get(k) for k, v in _FIXED.items() if c.get(k) != v}
    if off or c["rope_scaling"].get("type") != "yarn":
        raise ValueError(f"settings the program does not run: {off}")
    rs = c["rope_scaling"]
    kw = dict(
        name="deepseek-v2-lite", family="deepseek_v2",
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        d_ff=c["moe_intermediate_size"], vocab=c["vocab_size"],
        n_experts=c["n_routed_experts"],
        experts_per_tok=c["num_experts_per_tok"],
        norm_topk_prob=c["norm_topk_prob"],
        n_shared_experts=c["n_shared_experts"],
        first_k_dense_replace=c["first_k_dense_replace"],
        dense_d_ff=c["intermediate_size"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"],
        rope_theta=float(c["rope_theta"]),
        yarn_factor=float(rs["factor"]),
        yarn_original_max_position=rs["original_max_position_embeddings"],
        yarn_beta_fast=float(rs["beta_fast"]),
        yarn_beta_slow=float(rs["beta_slow"]),
        yarn_mscale=float(rs["mscale"]),
        yarn_mscale_all_dim=float(rs["mscale_all_dim"]),
        norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"],
        source="hf:deepseek-ai/DeepSeek-V2-Lite",
    )
    kw.update(overrides)
    return ArchConfig(**kw)


CONFIG = from_published(PUBLISHED)
