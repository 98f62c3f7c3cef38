"""The numbers that decide ``correct``, and how each is compared.

Training: the loss of each checked step, the first gradient as the
optimizer got it, and each parameter's change over the checked steps, all
against the plain reference run from the same weights on the same rows.
Parameters are judged one by one, stacked layer parameters layer by layer.
A norm is judged by the gap between the program's norm and the
reference's, over the larger of the reference's norm of that parameter and
of the median parameter; a direction by the distance between the program's
and the reference's unit vectors (0 for the same direction, 2 for the
opposite one). Parameters whose reference gradient is under a thousandth of
the median one's (nought to rounding: they move under Adam by round-off
alone) are left out.

The change is read by its worst parameter; the first gradient by its
median parameter, its norm and its direction, and, only to be shown, by its
worst (an SSD decay parameter, A_log or dt_bias, whose gradient the program
takes through a bfloat16 cumulative sum); the loss by its worst step. A
cell's ``limits`` name the numbers it compares; the others are shown.

Serving: the widest gap by which a served token's reference logit lies
below the reference's best logit at that position.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import weights

NEGLIGIBLE_GRAD = 1e-3


def leaf_norms(tree):
    """Norm of each parameter (stacked leaves cut into layers), as one vector."""
    return jnp.stack([jnp.linalg.norm(x.astype(jnp.float32).ravel())
                      for _, x in weights.leaf_slices(tree)])


def change_norms(cfg: dict, params, key):
    """Norm of each parameter's change from the weights ``key`` makes."""
    start = weights.init(cfg, key)
    return leaf_norms(jax.tree.map(lambda a, b: a - b, params, start))


def leaf_gaps(got, want, keep) -> np.ndarray:
    got, want = np.asarray(got, np.float64)[keep], np.asarray(want, np.float64)[keep]
    return np.abs(got - want) / np.maximum(want, np.median(want))


def gradient_readings(got, want) -> dict:
    """Per parameter of two gradient trees on the host: each side's norm, and
    the distance between their directions."""
    norms_got, norms_want, direction = [], [], []
    for (_, a), (_, b) in zip(weights.leaf_slices(got), weights.leaf_slices(want)):
        a = np.asarray(a, np.float64).ravel()
        b = np.asarray(b, np.float64).ravel()
        na, nb = np.linalg.norm(a), np.linalg.norm(b)
        norms_got.append(na)
        norms_want.append(nb)
        with np.errstate(divide="ignore", invalid="ignore"):
            direction.append(np.linalg.norm(a / na - b / nb))
    return {"got": np.asarray(norms_got), "want": np.asarray(norms_want),
            "direction": np.asarray(direction)}


def train_numbers(got: dict, want: dict) -> dict:
    """Compared and shown numbers of a training run ``got`` against the
    reference ``want``.

    Each side holds ``losses`` (one per checked step), ``grad`` (the first
    gradient, a tree on the host) and ``change`` (parameter change norms).
    """
    g = gradient_readings(got["grad"], want["grad"])
    keep = g["want"] >= NEGLIGIBLE_GRAD * np.median(g["want"])
    losses_got = np.asarray(got["losses"], np.float64)
    losses_want = np.asarray(want["losses"], np.float64)
    loss_gap = np.abs(losses_got - losses_want) / np.abs(losses_want)
    grad_gaps = leaf_gaps(g["got"], g["want"], keep)
    return {
        "grad_gap_median": _finite(np.median(grad_gaps)),
        "grad_direction_median": _finite(np.median(g["direction"][keep])),
        "change_gap": _finite(np.max(leaf_gaps(got["change"], want["change"], keep))),
        # Shown beside the compared numbers, not compared (see above).
        "loss_gap": _finite(np.max(loss_gap)),
        "grad_gap_worst": _finite(np.max(grad_gaps)),
        "grad_direction_worst": _finite(np.max(g["direction"][keep])),
    }


def _finite(x) -> float:
    x = float(x)
    return x if np.isfinite(x) else float("inf")


def served_gaps(ref_logits, tokens):
    """Per position: reference best logit minus the served token's logit."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    return best - got


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {value, limit}}): every number at or under its limit."""
    missing = set(limits) - set(numbers)
    if missing:
        raise ValueError(f"no reading for the limits {sorted(missing)}")
    shown = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"] for v in shown.values())
    return ok, shown
