"""The numbers that decide ``correct``: the program against the plain
reference's replay.

The run's first drains, through the window's own calls on the object the
window then drives:

* ``loss_gap``   -- over their updates, the largest relative gap of the
  client's mean local loss.
* ``grad_gap``   -- the first update as the server gets it (the delta),
  worst leaf: the gap between the program's leaf norm and the reference's,
  over the larger of the reference's norm of that leaf and of the median
  leaf.
* ``change_gap`` -- the same measure on the model's change over those
  drains.

What set-up ran through every program the window can reach, each from the
seed's weights:

* ``round_gap``  -- over every local round (each K, each client of each
  cohort bucket), the larger of the loss's relative gap and the worst-leaf
  gap of its update's norms.
* ``drain_gap``  -- over every drain size B, the worst-leaf gap of the
  drain's change of the model (cells with a drain window only).

Leaves whose reference update is nought to rounding (under a thousandth
of the median leaf's norm) are left out of every leaf measure.
"""
from __future__ import annotations

import math

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "change_gap", "round_gap", "drain_gap")
#: a leaf counts when its reference update norm is at least this share of
#: the median leaf's
LEAF_FLOOR = 1e-3


def leaf_gaps(prog, ref, ref_update) -> np.ndarray:
    """Each leaf's norm gap over the larger of its reference norm and the
    median leaf's; NaN for a leaf left out."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    ref_update = np.asarray(ref_update, np.float64)
    keep = ref_update >= LEAF_FLOOR * np.median(ref_update)
    base = np.maximum(ref, np.median(ref))
    return np.where(keep, np.abs(prog - ref) / base, np.nan)


def worst_leaf_gap(prog, ref, ref_update) -> float:
    return float(np.nanmax(leaf_gaps(prog, ref, ref_update)))


def worst_leaf(prog, ref, ref_update) -> int:
    """Index, in ``jax.tree.leaves`` order, of the leaf that sets the gap."""
    return int(np.nanargmax(leaf_gaps(prog, ref, ref_update)))


def loss_gaps(prog_losses, ref_losses) -> np.ndarray:
    lp = np.asarray(prog_losses, np.float64)
    lr = np.asarray(ref_losses, np.float64)
    return np.abs(lp - lr) / np.abs(lr)


def worst_update(prog_losses, ref_losses) -> int:
    """Index, in arrival order, of the update that sets the loss gap."""
    return int(np.argmax(loss_gaps(prog_losses, ref_losses)))


def round_gaps(prog_rounds, ref_rounds) -> list:
    return [max(abs(pl - rl) / abs(rl), worst_leaf_gap(pn, rn, rn))
            for (pl, pn), (rl, rn) in zip(prog_rounds, ref_rounds)]


def numbers(prog, ref) -> dict:
    """``prog`` and ``ref`` carry ``losses``, ``first_delta``, ``change``,
    ``rounds`` (loss, leaf norms) and ``drains`` (leaf norms); a number
    whose outputs are empty is left out."""
    out = {
        "loss_gap": float(np.max(loss_gaps(prog.losses, ref.losses))),
        "grad_gap": worst_leaf_gap(prog.first_delta, ref.first_delta,
                                   ref.first_delta),
        "change_gap": worst_leaf_gap(prog.change, ref.change,
                                     ref.first_delta),
    }
    if ref.rounds:
        out["round_gap"] = max(round_gaps(prog.rounds, ref.rounds))
    if ref.drains:
        out["drain_gap"] = max(worst_leaf_gap(p, r, r)
                               for p, r in zip(prog.drains, ref.drains))
    return out


def verdict(values: dict, limits: dict) -> bool:
    """Correct when the cell's numbers are the ones it has limits for, and
    each is finite and within its limit."""
    if set(values) != set(limits):
        raise ValueError(f"numbers {sorted(values)} against limits "
                         f"{sorted(limits)}")
    return all(math.isfinite(values[k]) and values[k] <= limits[k]
               for k in values)


def ordered(values: dict) -> list:
    return [k for k in NUMBERS if k in values]


def lines(values: dict, limits: dict) -> list:
    return [f"check {k} {values[k]!r} limit {limits[k]!r}"
            for k in ordered(values)]
