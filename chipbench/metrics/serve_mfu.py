"""serve_mfu: the forward operations of one decoded token over its live
context (chipbench/kinds/<kind>.py) times the traced window's tokens per
second by the host clock, over the chip's bf16 peak, in percent. Moves decode_tokens_per_s."""


def read(obs):
    if obs.get("kind") != "serve":
        return None
    return 100.0 * obs["flops_per_token"] * obs["tokens_per_s"] / obs["peaks"]["bf16_flops_per_s"]
