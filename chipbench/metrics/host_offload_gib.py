"""host_offload_gib: the timed train step's host-memory temporaries, as the
compiled executable reports them: the activations its remat policy sends to
pinned host memory each step and streams back. Moves train_tokens_per_s."""


def read(obs):
    if obs.get("kind") != "train":
        return None
    return obs["host_temp_bytes"] / 2**30
