"""deepseek-v2-lite-16b-ep8: one chip's share of deepseek-v2-lite-16b served
with each layer divided over 8 chips by expert parallelism (attention data
parallel): every head of MLA, both shared experts, and routed experts 0-7 of
the 64 that the router still routes over. Cut in depth with ``--layers`` it
is one pipeline stage, as the other architectures are.
"""

import dataclasses

from . import deepseek_v2_lite_16b as _published


def _share(cfg, name: str, ways: int):
    """Rank 0's share of ``cfg`` with its routed experts divided ``ways`` ways."""
    return dataclasses.replace(cfg, name=name, first_expert=0,
                               experts_held=cfg.num_experts // ways)


def config():
    return _share(_published.config(), "deepseek-v2-lite-16b-ep8", 8)


def smoke_config():
    # 2 of the smoke model's 8 experts, so that the held experts are a group.
    return _share(_published.smoke_config(), "deepseek-v2-lite-ep8-smoke", 4)
