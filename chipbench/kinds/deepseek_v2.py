"""DeepSeek-V2 decoder (deepseek-ai/DeepSeek-V2-Lite), one chip's share of
its routed experts: seeded weights, reference, and the operations and bytes
one decode step requires.

An operation is a multiply or an add (a multiply-accumulate counts 2). Only
the work the model requires is counted: attention in its absorbed form over
the latent cache, which is how a decode step needs it, and of the routed
experts the pairs that land on the experts held here.
"""

from __future__ import annotations

from chipbench.reference import deepseek_v2 as reference

__all__ = ["decode_flops_per_token", "decode_step_bytes", "dims", "init", "reference",
           "weight_count"]


def dims(c: dict) -> dict:
    return dict(D=c["hidden_size"], L=c["num_hidden_layers"], V=c["vocab_size"],
                H=c["num_attention_heads"], lora=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
                rope=c["qk_rope_head_dim"], vh=c["v_head_dim"], F=c["intermediate_size"],
                Fe=c["moe_intermediate_size"], held=c["n_routed_experts"],
                E=c["router_experts"], k=c["num_experts_per_tok"],
                Fs=c["n_shared_experts"] * c["moe_intermediate_size"],
                dense=c["first_k_dense_replace"])


def _swiglu(w, D, F, std, lead=()):
    return {"w_gate": w.normal((*lead, D, F), std), "w_up": w.normal((*lead, D, F), std),
            "w_down": w.normal((*lead, F, D), std)}


def init(c: dict, w):
    """Parameters from the leaf source ``w`` (``chipbench.weights``): the dense
    layers, then the MoE layers stacked; every linear layer, the embedding and
    the head drawn with the configuration's initializer_range."""
    d = dims(c)
    D, H, lora, std = d["D"], d["H"], d["lora"], c["initializer_range"]
    if d["dense"] != 1:
        raise ValueError(f"{c['name']}: the program's layout holds one leading dense layer")

    def layer(lead, ffn):
        return {
            "ln1": {"scale": w.scale((*lead, D))},
            "attn": {
                "wq": w.normal((*lead, D, H, d["nope"] + d["rope"]), std),
                "w_dkv": w.normal((*lead, D, lora), std),
                "kv_norm": w.scale((*lead, lora)),
                "w_kr": w.normal((*lead, D, d["rope"]), std),
                "w_uk": w.normal((*lead, lora, H, d["nope"]), std),
                "w_uv": w.normal((*lead, lora, H, d["vh"]), std),
                "wo": w.normal((*lead, H, d["vh"], D), std),
            },
            "ln2": {"scale": w.scale((*lead, D))},
            **ffn(lead),
        }

    def moe(lead):
        return {"moe": {"router": w.normal((*lead, D, d["E"]), std),
                        **_swiglu(w, D, d["Fe"], std, (*lead, d["held"])),
                        "shared": _swiglu(w, D, d["Fs"], std, lead)}}

    L = d["L"] - d["dense"]
    return {
        "embed": {"tok": w.normal((d["V"], D), std), "head": w.normal((d["V"], D), std)},
        "blocks": [{"l0": layer((), lambda lead: {"ffn": _swiglu(w, D, d["F"], std, lead)})},
                   {"l0": layer((L,), moe)}],
        "final_norm": {"scale": w.scale((D,))},
    }


def _attention_weights(d) -> int:
    D, H, lora = d["D"], d["H"], d["lora"]
    return (D * H * (d["nope"] + d["rope"]) + D * lora + lora + D * d["rope"]
            + lora * H * (d["nope"] + d["vh"]) + H * d["vh"] * D + 2 * D)


def weight_count(c: dict, experts_touched: float | None = None) -> float:
    """Parameters held: the layers' matrices and norms, the final norm, the
    embedding and the untied head. With ``experts_touched``, the routed
    experts counted are that many a MoE layer instead of all held."""
    d = dims(c)
    D = d["D"]
    held = d["held"] if experts_touched is None else experts_touched
    moe = D * d["E"] + held * 3 * D * d["Fe"] + 3 * D * d["Fs"]
    layers = d["L"] * _attention_weights(d) + d["dense"] * 3 * D * d["F"]
    return layers + (d["L"] - d["dense"]) * moe + 2 * d["V"] * D + D


def decode_flops_per_token(c: dict, context: float) -> float:
    """Forward of one new token attending over ``context`` positions, MLA
    absorbed: scores over the latent and rope key, context over the latent."""
    d = dims(c)
    D, H, lora = d["D"], d["H"], d["lora"]
    attention = (
        2 * D * H * (d["nope"] + d["rope"])   # q projection
        + 2 * D * (lora + d["rope"])          # latent compression and rope key
        + 2 * H * d["nope"] * lora            # W_uk absorbed into q
        + 2 * H * context * (lora + d["rope"])  # scores
        + 2 * H * context * lora              # context over the latent
        + 2 * H * lora * d["vh"]              # W_uv
        + 2 * H * d["vh"] * D                 # Wo
    )
    # A token's top-k pairs land on the held experts at held / router's share.
    routed = d["k"] * d["held"] / d["E"]
    moe = 2 * D * d["E"] + routed * 2 * D * d["Fe"] * 3 + 2 * D * d["Fs"] * 3
    L = d["L"]
    return L * attention + d["dense"] * 2 * D * d["F"] * 3 + (L - d["dense"]) * moe + 2 * D * d["V"]


def experts_touched(c: dict, batch: int) -> float:
    """Held experts a batch's tokens are expected to route to, a MoE layer:
    each expert is missed by a token with probability 1 - k / router."""
    d = dims(c)
    return d["held"] * (1 - (1 - d["k"] / d["E"]) ** batch)


def decode_step_bytes(c: dict, batch: int, context: float) -> float:
    """Least bytes one decode step moves: every weight once at bfloat16 (the
    checkpoint's type), of the routed experts those the batch is expected to
    touch; the live latent and rope key positions read, and one position
    written, at the cache's bfloat16."""
    d = dims(c)
    per_position = d["L"] * (d["lora"] + d["rope"]) * 2
    return weight_count(c, experts_touched(c, batch)) * 2 + batch * (context + 1) * per_position
