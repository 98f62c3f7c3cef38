"""decode_hbm_roofline_share: the least bytes one decode step must move
(chipbench/kinds/<kind>.py: weights once at bf16, the live KV positions read, one
written) over the chip's HBM bandwidth, over the step program's mean device
time in the trace, in percent. Moves decode_tokens_per_s."""

def read(obs):
    if obs.get("kind") != "serve" or not obs.get("trace"):
        return None
    times = [t for name, ts in obs["trace"]["module_s"].items()
             if obs["step_name"] in name for t in ts]
    if not times:
        return None
    least_s = obs["step_bytes"] / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (sum(times) / len(times))
