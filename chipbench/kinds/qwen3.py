"""Qwen3 decoder (Qwen/Qwen3-4B): seeded weights, reference, and the
operations and bytes one decode step requires.

An operation is a multiply or an add (a multiply-accumulate counts 2). Only
the work the model requires is counted.
"""

from __future__ import annotations

from chipbench.reference import qwen3 as reference

__all__ = ["decode_flops_per_token", "decode_step_bytes", "dims", "init", "reference",
           "weight_count"]


def dims(c: dict) -> dict:
    return dict(D=c["hidden_size"], L=c["num_hidden_layers"], V=c["vocab_size"],
                H=c["num_attention_heads"], KV=c["num_key_value_heads"], hd=c["head_dim"],
                F=c["intermediate_size"])


def init(c: dict, w):
    """Parameters from the leaf source ``w`` (``chipbench.weights``), stacked
    per layer; the linear layers drawn with Qwen3's initializer_range."""
    d = dims(c)
    D, L, H, KV, hd, F = d["D"], d["L"], d["H"], d["KV"], d["hd"], d["F"]
    std = c["initializer_range"]
    layer = {
        "ln1": {"scale": w.scale((L, D))},
        "attn": {
            "wq": w.normal((L, D, H, hd), std),
            "wk": w.normal((L, D, KV, hd), std),
            "wv": w.normal((L, D, KV, hd), std),
            "wo": w.normal((L, H, hd, D), std),
            "q_norm": w.scale((L, hd)),
            "k_norm": w.scale((L, hd)),
        },
        "ln2": {"scale": w.scale((L, D))},
        "ffn": {
            "w_gate": w.normal((L, D, F), std),
            "w_up": w.normal((L, D, F), std),
            "w_down": w.normal((L, F, D), std),
        },
    }
    return {
        "embed": {"tok": w.normal((d["V"], D), std)},
        "blocks": [{"l0": layer}],
        "final_norm": {"scale": w.scale((D,))},
    }


def weight_count(c: dict) -> int:
    """Parameters: the layers' matrices and norms, the final norm and the
    tied embedding."""
    d = dims(c)
    D, H, KV, hd, F = d["D"], d["H"], d["KV"], d["hd"], d["F"]
    per_layer = D * H * hd * 2 + D * KV * hd * 2 + 3 * D * F + 2 * D + 2 * hd
    return d["L"] * per_layer + d["V"] * D + D


def decode_flops_per_token(c: dict, context: float) -> float:
    """Forward of one new token attending over ``context`` positions."""
    d = dims(c)
    D, H, KV, hd, F = d["D"], d["H"], d["KV"], d["hd"], d["F"]
    per_layer = (
        2 * D * H * hd * 2          # q and o projections
        + 2 * D * KV * hd * 2       # k and v projections
        + 2 * D * F * 3             # SwiGLU
        + 2 * H * hd * context * 2  # q.k and p.v over the live positions
    )
    return d["L"] * per_layer + 2 * D * d["V"]


def decode_step_bytes(c: dict, batch: int, context: float) -> float:
    """Least bytes one decode step moves: every weight once at bfloat16 (the
    checkpoint's type), the live keys and values read, and one position of
    keys and values written, at the cache's bfloat16."""
    d = dims(c)
    kv_per_position = d["L"] * 2 * d["KV"] * d["hd"] * 2
    return weight_count(c) * 2 + batch * (context + 1) * kv_per_position
