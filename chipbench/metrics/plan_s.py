"""plan_s: host seconds to build the memory planner and solve (or restore
from the plan cache) the cell's offload plan, in set-up. Moves setup_s."""


def read(obs):
    return obs.get("plan_s")
