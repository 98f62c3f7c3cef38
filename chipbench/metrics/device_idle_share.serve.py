"""device_idle_share.serve: percent of the traced window in which no
operation ran on the chip (profiler trace). Moves decode_tokens_per_s."""


def read(obs):
    if obs.get("kind") != "serve" or not obs.get("trace"):
        return None
    return 100.0 * obs["trace"]["idle_share"]
