"""Architecture configuration dataclass shared by the whole zoo.

One frozen dataclass describes every assigned architecture; family-specific
fields are simply unused elsewhere.  Configs are constructed in
``repro/configs/<arch>.py`` (exact assigned hyperparameters, with source
citations) and each provides a ``reduced()`` smoke variant
(<=2 layers, d_model <= 512, <= 4 experts) per the assignment contract.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Tuple

__all__ = ["ArchConfig", "InputShape", "SHAPES"]


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                      # dense | moe | deepseek_v2 | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0                # 0 -> d_model // n_heads

    # --- MoE ---------------------------------------------------------------
    n_experts: int = 0               # routed experts the router scores
    experts_per_tok: int = 0
    capacity_factor: float = 1.25
    #: experts this chip holds, 0..experts_held-1 (expert parallelism: the
    #: layer routes over all n_experts and computes its own experts' part
    #: of the result); 0 -> all n_experts
    experts_held: int = 0
    norm_topk_prob: bool = True      # renormalise the top-k gates to sum 1
    #: always-on SwiGLU experts of width d_ff each, added to the routed
    #: output (deepseek_v2: one SwiGLU of width n_shared_experts * d_ff)
    n_shared_experts: int = 0
    #: leading layers whose FFN is a dense SwiGLU of width dense_d_ff
    #: instead of the expert layer (deepseek_v2)
    first_k_dense_replace: int = 0
    dense_d_ff: int = 0

    # --- multi-head latent attention (deepseek_v2; kv_lora_rank > 0) ---------
    kv_lora_rank: int = 0            # width of the normed latent c
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0        # one rotary key shared by all heads
    v_head_dim: int = 0
    #: YaRN rotary scaling of the rope dims (0 -> plain RoPE)
    yarn_factor: float = 0.0
    yarn_original_max_position: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    # --- attention pattern --------------------------------------------------
    sliding_window: int = 0          # 0 = full attention
    #: repeating per-layer pattern; entries in {"global", "local", "rec"}.
    #: () -> all "global".  gemma3: ("local",)*5 + ("global",)
    #: recurrentgemma: ("rec", "rec", "local")
    layer_pattern: Tuple[str, ...] = ()

    # --- positions ----------------------------------------------------------
    pos_type: str = "rope"           # rope | mrope | none | learned
    rope_theta: float = 10000.0

    # --- encoder-decoder (whisper) -------------------------------------------
    encoder_layers: int = 0
    encoder_seq: int = 1500          # conv-frontend output frames (stubbed)

    # --- vlm (qwen2-vl) -------------------------------------------------------
    vision_tokens: int = 0           # stub patch-embedding prefix length

    # --- ssm (rwkv6) ----------------------------------------------------------
    rwkv_head_dim: int = 64
    time_decay_extra_dim: int = 64   # lora dim for data-dependent decay

    # --- hybrid (recurrentgemma) -----------------------------------------------
    d_rnn: int = 0                   # RG-LRU width (0 -> d_model)
    conv_width: int = 4              # temporal conv1d in recurrent block

    norm_eps: float = 1e-6
    scale_embed: bool = False        # gemma-style sqrt(d) embedding scaling
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    remat: bool = True               # activation-checkpoint each layer
    attn_chunk: int = 1024           # kv-chunk for memory-bounded attention
    scan_chunk: int = 256            # time-chunk for recurrent families
    #: unroll factor for the scan over layers.  1 = compact HLO (production);
    #: n_layers = fully unrolled (used by the dry-run cost lowerings so that
    #: cost_analysis counts every layer -- see DESIGN.md Sec. 6).
    scan_unroll: int = 1
    #: unroll the inner query-chunk / loss-chunk scans too (cost lowerings
    #: only -- exact flop counting with production memory access pattern).
    attn_unroll: bool = False
    #: sequence-chunk size for the vocab cross-entropy (bounds the live
    #: logits to (B, ce_chunk, V); the backward recomputes per chunk).
    ce_chunk: int = 512
    # ---- SPerf hillclimb switches (default False = paper-faithful /
    # naive baseline; EXPERIMENTS.md SPerf records before/after) ----------
    #: stop gradients through the MoE dispatch/combine one-hot structure
    #: (router still learns via the gate values); kills the f32 (S, E, C)
    #: cotangent all-gathers in the backward.
    moe_stop_gradient_dispatch: bool = False
    #: pad embed/head vocab to a multiple of this so the head shards over
    #: "model" (Megatron-style); 0 = no padding.
    pad_vocab_multiple: int = 0
    #: MoE dispatch group size (tokens); smaller groups shrink the
    #: (S_g, E, C) one-hots quadratically per group.
    moe_group: int = 4096
    #: contract grouped K/V directly instead of materializing repeat_kv
    #: (H/KV-times less K/V HBM traffic).
    gqa_native: bool = False
    #: force the FL-round grad-accumulation microbatch count (0 = auto from
    #: the activation-memory budget).  Fewer microbatches = fewer FSDP
    #: weight re-gathers/re-streams per round, at more activation memory.
    grad_accum_override: int = 0
    source: str = ""                 # citation for the exact config

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def held(self) -> int:
        """Routed experts held on this chip."""
        return self.experts_held or self.n_experts

    @property
    def pattern(self) -> Tuple[str, ...]:
        return self.layer_pattern or ("global",)

    def layer_kinds(self, n: int | None = None) -> Tuple[str, ...]:
        """Expand the repeating pattern over n layers."""
        n = n or self.n_layers
        pat = self.pattern
        return tuple(pat[i % len(pat)] for i in range(n))

    def reduced(self) -> "ArchConfig":
        """Smoke-test variant: same family/topology, tiny dimensions."""
        d = min(self.d_model, 256)
        heads = min(self.n_heads, 4)
        kv = min(self.n_kv_heads, heads)
        hd = d // heads
        pat = self.pattern
        n_layers = max(2, len(pat)) if len(pat) > 1 else 2
        return dataclasses.replace(
            self,
            name=self.name + "-smoke",
            n_layers=n_layers,
            d_model=d,
            n_heads=heads,
            n_kv_heads=kv,
            head_dim=hd if self.head_dim else 0,
            d_ff=min(self.d_ff, 512),
            vocab=min(self.vocab, 1024),
            n_experts=min(self.n_experts, 4) if self.n_experts else 0,
            experts_per_tok=min(self.experts_per_tok, 2) if self.experts_per_tok else 0,
            experts_held=0,
            first_k_dense_replace=min(self.first_k_dense_replace, 1),
            dense_d_ff=min(self.dense_d_ff, 512),
            kv_lora_rank=min(self.kv_lora_rank, 64),
            qk_nope_head_dim=min(self.qk_nope_head_dim, 32),
            qk_rope_head_dim=min(self.qk_rope_head_dim, 16),
            v_head_dim=min(self.v_head_dim, 32),
            encoder_layers=min(self.encoder_layers, 2) if self.encoder_layers else 0,
            encoder_seq=min(self.encoder_seq, 64) if self.encoder_layers else self.encoder_seq,
            vision_tokens=min(self.vision_tokens, 16) if self.vision_tokens else 0,
            sliding_window=min(self.sliding_window, 16) if self.sliding_window else 0,
            d_rnn=min(self.d_rnn, 256) if self.d_rnn else 0,
            time_decay_extra_dim=16,
            attn_chunk=64,
            scan_chunk=16,
            dtype="float32",
            remat=False,
        )


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                        # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}
