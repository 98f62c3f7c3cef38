"""Training cells, whole runs at smoke size with the device check left out:
a sound run comes out correct; one whose timed step is broken underneath
comes out not correct."""

import jax
import pytest

from chipbench.tests import cells

TRAIN = [c for c in cells.CELLS if ".train." in c]


def unchanged_state(fn):
    def step(params, opt, batch, k):
        return params, opt, fn(params, opt, batch, k)[2]

    return step


def half_batch(fn):
    def step(params, opt, batch, k):
        return fn(params, opt, {n: x[: x.shape[0] // 2] for n, x in batch.items()}, k)

    return step


def doubled_update(fn):
    """The step's answer altered where it is produced: every parameter moves
    twice as far as the step says."""
    def step(params, opt, batch, k):
        new, opt, met = fn(params, opt, batch, k)
        return jax.tree.map(lambda p, q: p + 2 * (q - p), params, new), opt, met

    return step


@pytest.mark.parametrize("name", TRAIN)
def test_a_sound_train_run_is_correct(name):
    r = cells.run_small(name)
    assert r["correct"] is True, r["checks"]


@pytest.mark.parametrize("fault", [unchanged_state, half_batch, doubled_update])
@pytest.mark.parametrize("name", TRAIN)
def test_a_broken_train_step_is_not_correct(name, fault):
    r = cells.run_small(name, fault)
    assert r["correct"] is False, r["checks"]

