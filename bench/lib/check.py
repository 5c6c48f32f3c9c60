"""The numbers that decide ``correct``: the program's first rounds against
the plain reference's.

Rounds are the steps of this training loop.  They change the server's
weights and filter scales (trained) and its batch-norm running statistics
(the clients' statistics, averaged), and end in the test accuracy that
``Evaluate`` reports.  Per leaf, the change of the server state after the
first round and after the last compared round; a leaf's gap is the
reference's norm of its change less the program's, over the larger of the
reference's norm of that leaf and the median reference norm of the leaves
compared with it:

  weight_update_gap     the median absolute gap of the first round's
                        change over the weight tensors (conv and dense
                        kernels, two or more dimensions: all but a few
                        thousand of the parameters)
  weight_update_shrink  the median signed gap over the same tensors:
                        positive where the program's updates fall short of
                        the reference's.  Lower precision rounds small
                        steps away and shrinks every update alike, where
                        the float32 round-off of sound runs moves them
                        either way.
  scale_update_gap      the median absolute gap of the first round's
                        change over the filter scales (the scale
                        sub-epochs and their accept-if-improves step)
  bn_gap                the median absolute gap of the first round's
                        change over the batch-norm running means and
                        variances (the fold of the clients' statistics
                        that ``Evaluate`` uses)
  update_gap            the median absolute gap of the first round's
                        change over all trained leaves (weights, biases,
                        batch-norm scales and shifts, filter scales)
  change_gap            the median absolute gap of the change over all the
                        compared rounds, over the trained leaves
  acc_gap               the largest gap of test accuracy over the compared
                        rounds (what ``Evaluate`` reports)
  loss_gap              the relative gap of the first round's mean
                        training loss

The median and not the worst leaf: the fsfl round is chaotic in float32
(top-k selection and accept-if-improves decided by rounding), so a small
leaf (a layer's 32 filter scales) reads a gap of about 0.1 on every seed
from the noise of its few entries alone, while the median leaf is steady
from seed to seed.  Trained leaves whose first-round change in the
reference is under a thousandth of the median trained leaf's move by
round-off alone (the scalar scale placeholders of tensors that carry no
filter scales) and are left out.

A cell's limits file names the numbers it compares; the others are
reported beside them where neither the control nor a fault moves them
past what sound runs read (PERF.md).
"""
from __future__ import annotations

import math

import numpy as np

NOUGHT = 1e-3
TRAINED = ("params", "scales")
TREES = TRAINED + ("bn",)


def leaf_stats(before: dict, after: dict) -> dict[str, tuple]:
    """``(norm of the change, dimensions)`` of each leaf of the server
    state, keyed by its path."""
    out = {}

    def walk(prefix, a, b):
        if isinstance(a, dict):
            for k in a:
                walk(f"{prefix}/{k}", a[k], b[k])
        else:
            d = np.asarray(b, np.float64) - np.asarray(a, np.float64)
            out[prefix] = (float(np.linalg.norm(d)), d.ndim)

    for t in TREES:
        walk(t, before[t], after[t])
    return out


def _gaps(prog: dict, ref: dict, keep: list[str]) -> list[float]:
    """Each kept leaf's signed gap, the reference's norm of its change less
    the program's, over the larger of the reference's norm and the median
    reference norm of the kept leaves."""
    if set(prog) != set(ref):
        raise ValueError("the program's state and the reference's differ in "
                         f"their leaves: {sorted(set(prog) ^ set(ref))}")
    if not keep:
        raise ValueError("no leaves to compare")
    median = float(np.median([ref[k][0] for k in keep]))
    return [(ref[k][0] - prog[k][0]) / max(ref[k][0], median) for k in keep]


def _median_gap(prog: dict, ref: dict, keep: list[str]) -> float:
    return float(np.median(np.abs(_gaps(prog, ref, keep))))


def compare(prog: dict, ref: dict) -> dict[str, float]:
    """``prog`` and ``ref`` each hold ``loss`` and ``acc`` (per round),
    ``state0``, ``state1`` and ``state_last``."""
    return numbers(
        leaf_stats(prog["state0"], prog["state1"]),
        leaf_stats(ref["state0"], ref["state1"]),
        leaf_stats(prog["state0"], prog["state_last"]),
        leaf_stats(ref["state0"], ref["state_last"]), prog, ref)


def numbers(prog1: dict, ref1: dict, prog_last: dict, ref_last: dict,
            prog: dict, ref: dict) -> dict[str, float]:
    """The numbers from the leaves' changes after the first round
    (``*1``) and the last (``*_last``), and the runs' ``acc`` and
    ``loss``."""
    trained = [k for k in ref1 if k.split("/")[0] in TRAINED]
    median1 = float(np.median([ref1[k][0] for k in trained]))
    keep = sorted(k for k in trained if ref1[k][0] >= NOUGHT * median1)
    weights = [k for k in keep if k.startswith("params/") and ref1[k][1] >= 2]
    scales = [k for k in keep if k.startswith("scales/")]
    bn = sorted(k for k in ref1 if k.startswith("bn/"))
    out = {
        "weight_update_gap": _median_gap(prog1, ref1, weights),
        "weight_update_shrink": float(np.median(_gaps(prog1, ref1,
                                                      weights))),
        "scale_update_gap": _median_gap(prog1, ref1, scales),
        "bn_gap": _median_gap(prog1, ref1, bn),
        "update_gap": _median_gap(prog1, ref1, keep),
        "change_gap": _median_gap(prog_last, ref_last, keep),
        "acc_gap": max(abs(a - b) for a, b in zip(prog["acc"], ref["acc"],
                                                  strict=True)),
        "loss_gap": abs(prog["loss"][0] - ref["loss"][0])
        / abs(ref["loss"][0]),
    }
    # a reading that is not a number (a run that diverged) fails
    return {k: (v if math.isfinite(v) else math.inf) for k, v in out.items()}


def verdict(numbers: dict[str, float], limits: dict[str, float]) -> bool:
    missing = set(limits) - set(numbers)
    if missing:
        raise KeyError(f"no reading for the limits {sorted(missing)}")
    return all(numbers[k] <= limits[k] for k in limits)
