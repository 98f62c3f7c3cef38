"""compile_s: host seconds that JAX spent lowering and compiling (or loading
from the persistent cache) every program of the set-up. Moves setup_s."""


def read(obs):
    return obs.get("compile_s")
