"""Serving cells, whole runs at smoke size with the device check left out:
a sound run comes out correct; one whose decode step is broken underneath,
or with the float8 control in the program's place, comes out not correct."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.control import frozen_cache
from chipbench.reference.common import CONTROL
from chipbench.run import _driver
from chipbench.tests import cells

SERVE = [c for c in cells.CELLS if ".serve." in c]


def altered_token(fn):
    def step(params, cache, tok, pos):
        tok, cache = fn(params, cache, tok, pos)
        return jnp.where(pos % 3 == 0, (tok + 1) % cells.QWEN3["vocab_size"], tok), cache

    return step


@pytest.mark.parametrize("name", SERVE)
def test_a_sound_serve_run_is_correct(name):
    r = cells.run_small(name)
    assert r["correct"] is True, r["checks"]


@pytest.mark.parametrize("fault", [frozen_cache, altered_token])
@pytest.mark.parametrize("name", SERVE)
def test_a_broken_decode_step_is_not_correct(name, fault):
    r = cells.run_small(name, fault)
    assert r["correct"] is False, r["checks"]
    assert r["failed"] >= 1


@pytest.mark.parametrize("name", SERVE)
def test_the_float8_control_fails_the_serve_limit(name):
    wl, cfg = cells.small_cell(name)
    drv = _driver("serve")
    traffic = drv.Traffic(cfg, wl, 5)
    rng = np.random.default_rng(5)
    requests = []
    for turn in range(wl["check_requests"]):
        served = rng.integers(0, cfg["vocab_size"], wl["turn_len"]).astype(np.int32)
        requests.append((drv.served_sequence(traffic, turn, 0, served), served))
    with jax.default_matmul_precision("highest"):
        gaps = drv.reference_gaps(cfg, wl, 5, requests, rank_by=CONTROL)
    assert max(float(np.max(g)) for g in gaps) > wl["limits"]["served_gap"]
