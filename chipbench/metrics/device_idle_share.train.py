"""device_idle_share.train: percent of the traced window in which no
operation ran on the chip (profiler trace). Moves train_tokens_per_s."""


def read(obs):
    if obs.get("kind") != "train" or not obs.get("trace"):
        return None
    return 100.0 * obs["trace"]["idle_share"]
