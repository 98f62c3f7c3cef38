"""Model/config schema shared by all assigned architectures.

A model is a *program* of layer segments.  Each segment is a unit of one or
more ``LayerSpec``s repeated R times; units with R > 1 are executed under
``jax.lax.scan`` with stacked parameters (keeps HLO small for 30-50-layer
models), units with R == 1 are applied directly.  This representation covers
every assigned pattern exactly:

  qwen3-4b      [(full,) x 36]
  gemma3-4b     [(l,l,l,l,l,g) x 5, (l,l,l,l) x 1]       5:1 local:global
  gemma2-9b     [(l,g) x 21]                              alternating
  llama4        [(moe, dense) x 24]                       interleaved MoE
  deepseek-v2   [(dense-mla,) x 1, (moe-mla,) x 26]       first layer dense
  hymba         [(hg,) 1, (hl,) 15, (hg,) 1, (hl,) 14, (hg,) 1]
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Literal

AttnKind = Literal["full", "window", "mla", "mamba", "hybrid", "none"]
FFNKind = Literal["dense", "moe", "none"]


@dataclass(frozen=True)
class LayerSpec:
    attn: AttnKind = "full"
    ffn: FFNKind = "dense"
    window: int | None = None     # sliding-window width when attn in (window, hybrid)
    cross_attn: bool = False      # decoder layers of enc-dec models


# A program segment: (unit of layer specs, repeat count).
Segment = tuple[tuple[LayerSpec, ...], int]


@dataclass(frozen=True)
class YarnScaling:
    """YaRN rope scaling (arXiv:2309.00071), as DeepSeek-V2 publishes it under
    ``rope_scaling``: the rope frequencies stretched by ``factor`` beyond
    ``original_max_position``, and the attention scaled by mscale."""

    factor: float
    original_max_position: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | vlm | ssm | audio | hybrid
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    program: tuple[Segment, ...]

    # ---- attention options ----
    qk_norm: bool = False
    attn_softcap: float | None = None
    final_softcap: float | None = None
    rope_theta: float = 10_000.0
    mrope_sections: tuple[int, int, int] | None = None  # M-RoPE (qwen2-vl)
    attn_scale: float | None = None                     # override 1/sqrt(hd)
    rope_scaling: YarnScaling | None = None

    # ---- MLA (deepseek) ----
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # ---- MoE ----
    num_experts: int = 0          # the router's outputs: every routed expert
    num_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    capacity_factor: float = 1.25  # the shard_map expert-parallel path only
    router_type: str = "softmax"  # softmax | sigmoid (llama4 top-1)
    norm_topk_prob: bool = True   # renormalize the top-k softmax gates to sum 1
    # The routed experts this chip holds: ids first_expert .. first_expert +
    # experts_held - 1 of the num_experts routed over (0: all of them).
    first_expert: int = 0
    experts_held: int = 0

    # ---- FFN ----
    ffn_act: str = "swiglu"       # swiglu | gelu

    # ---- SSM (mamba2 / hymba) ----
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_chunk: int = 64
    ssm_groups: int = 1
    conv_kernel: int = 4

    # ---- enc-dec (whisper) ----
    is_encoder_decoder: bool = False
    enc_program: tuple[Segment, ...] = ()
    enc_seq: int = 0              # encoder frames (post-frontend stub)

    # ---- frontends (stubs: input_specs() provides the embeddings) ----
    frontend: str | None = None   # vision_stub | audio_stub
    num_patch_tokens: int = 0     # vlm: patch embeddings prepended to the text

    # ---- misc ----
    tie_embeddings: bool = True
    norm_eps: float = 1e-6
    norm_type: str = "rms"        # rms | layer (whisper)
    scale_embed: bool = False     # gemma-style sqrt(d_model) embedding scale
    dtype: str = "bfloat16"
    # post-attn/post-ffn extra norms (gemma2/gemma3 style sandwich norms)
    sandwich_norms: bool = False

    def __post_init__(self):
        n = sum(len(unit) * reps for unit, reps in self.program)
        if n != self.num_layers:
            raise ValueError(
                f"{self.name}: program covers {n} layers, config says {self.num_layers}"
            )
        if self.first_expert + self.num_held_experts > self.num_experts:
            last = self.first_expert + self.num_held_experts - 1
            raise ValueError(
                f"{self.name}: experts {self.first_expert}..{last} held, "
                f"the router has {self.num_experts}"
            )

    @property
    def num_held_experts(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def d_inner(self) -> int:  # mamba inner width
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_headdim

    def reduced(self, **overrides) -> "ModelConfig":
        """A smoke-test-scale sibling of the same family (see configs/<arch>.py)."""
        return dataclasses.replace(self, **overrides)


@dataclass(frozen=True)
class ShapeSpec:
    """One assigned input-shape cell."""

    name: str                     # train_4k | prefill_32k | decode_32k | long_500k
    seq_len: int
    global_batch: int
    kind: Literal["train", "prefill", "decode"]


SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}


def uniform_program(spec: LayerSpec, n: int) -> tuple[Segment, ...]:
    return ((tuple([spec]), n),)


def cut_depth(cfg: ModelConfig, layers: int) -> ModelConfig:
    """The first ``layers`` layers of ``cfg`` at unchanged widths.

    The program is cut in whole periods: a segment's unit (one layer for a
    uniform program, the local/global pattern for gemma) is never split, so
    the cut model keeps the published layer mix.  The encoder of an
    encoder-decoder model keeps its depth.
    """
    if not 1 <= layers <= cfg.num_layers:
        raise ValueError(f"{cfg.name}: --layers {layers} not in [1, {cfg.num_layers}]")
    program: list[Segment] = []
    left = layers
    for unit, reps in cfg.program:
        if left == 0:
            break
        take = min(reps, left // len(unit))
        if take * len(unit) < min(left, reps * len(unit)):
            raise ValueError(
                f"{cfg.name}: --layers {layers} splits a period of {len(unit)} layers"
            )
        program.append((unit, take))
        left -= take * len(unit)
    return dataclasses.replace(cfg, num_layers=layers, program=tuple(program))
