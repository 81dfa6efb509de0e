"""The comparison that decides ``correct``, and how its numbers print.

Training: the program's first two steps against the plain reference's.
The loss, as a share of the reference's, by the worse of the two steps.
The norm of the first gradient and the norm of the parameters' change
over the steps, by the worst leaf: the gap between the program's norm of
a leaf and the reference's (not the norm of their difference), against
the reference's norm of that leaf or of the median leaf, whichever is
larger. A leaf whose reference gradient is under a thousandth of the
median leaf's is left out of the change: Adam moves such a leaf by
round-off alone. And the first gradient once more with each side's
leaves divided by that side's whole norm (``grad_share_gap``): a factor
common to all leaves, such as a clip that divides by a norm rounded to
bfloat16, drops out, and what is left is how the gradient is spread over
the leaves.
"""

from __future__ import annotations

import numpy as np


def flat(norms: dict) -> dict:
    """{leaf name: [norm of each layer]} -> {"name[layer]": norm}."""
    return {f"{name}[{i}]": float(v)
            for name, values in sorted(norms.items())
            for i, v in enumerate(np.atleast_1d(values))}


def leaf_gaps(program: dict, reference: dict, keep=None) -> dict:
    """{leaf: gap} over the leaves of ``reference`` (in ``keep``)."""
    ref = flat(reference)
    got = flat(program)
    names = [n for n in ref if keep is None or n in keep]
    median = float(np.median([ref[n] for n in names]))
    out = {}
    for n in names:
        gap = abs(got.get(n, 0.0) - ref[n]) / max(ref[n], median, 1e-30)
        out[n] = gap if np.isfinite(gap) else float("inf")
    return out


def worst_leaf_gap(program: dict, reference: dict, keep=None):
    """(gap, leaf) of the leaf that reads worst."""
    gaps = leaf_gaps(program, reference, keep)
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def shares(norms: dict) -> dict:
    """Every leaf's norm over the norm of all the leaves together."""
    whole = float(np.sqrt(sum(float(np.square(v).sum())
                              for v in norms.values())))
    return {k: np.asarray(v) / max(whole, 1e-30) for k, v in norms.items()}


def training_numbers(program: dict, reference: dict) -> dict:
    """{name: value} of the numbers compared; ``program`` and
    ``reference`` are what ``follow_two_steps`` returns."""
    out = {}
    for i, (a, r) in enumerate(zip(program["loss"], reference["loss"])):
        gap = abs(a - r) / abs(r)
        out[f"loss_gap_step{i + 1}"] = gap if np.isfinite(gap) else float("inf")
    out["loss_gap"] = max(v for k, v in out.items() if k.startswith("loss"))
    out["first_grad_gap"], out["first_grad_leaf"] = worst_leaf_gap(
        program["first_grad"], reference["first_grad"])
    out["grad_share_gap"], out["grad_share_leaf"] = worst_leaf_gap(
        shares(program["first_grad"]), shares(reference["first_grad"]))
    grads = flat(reference["first_grad"])
    floor = 1e-3 * float(np.median(list(grads.values())))
    moved = {n for n, g in grads.items() if g >= floor}
    out["change_gap"], out["change_leaf"] = worst_leaf_gap(
        program["change"], reference["change"], keep=moved)
    return out


def judge(numbers: dict, limits: dict):
    """(correct, {name: {"value", "limit"}}) for the numbers with a limit."""
    compared = {name: {"value": numbers[name], "limit": limit}
                for name, limit in limits.items()}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    return correct, compared
