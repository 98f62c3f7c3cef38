"""train_mfu: the model's forward and backward operations per token
(chipbench/kinds/<kind>.py, recomputation not counted) times the traced
window's tokens per second by the host clock, over the chip's bf16 peak, in
percent. Moves
train_tokens_per_s."""


def read(obs):
    if obs.get("kind") != "train":
        return None
    return 100.0 * obs["flops_per_token"] * obs["tokens_per_s"] / obs["peaks"]["bf16_flops_per_s"]
