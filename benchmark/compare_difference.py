"""``compare.training_numbers`` and one number more, for a cell whose
worst leaf's norm moves with something other than the products'
precision.

``compare``'s gradient numbers set the program's NORM of a leaf against
the reference's. Rounding the operands of a product leaves a norm nearly
where it was (errors of relative size e in random directions lengthen a
vector by e * e / 2), so 8-bit operands move those numbers by a few
thousandths, and in a stack whose router chooses 22 of 512 near-equal
scores the program's own worst leaf moves as far from seed to seed with
the near ties (PERF.md section 6, PR 36). The norm of the DIFFERENCE of
the two gradients is of the first order in the rounding.

``first_grad_diff``: for every leaf the norm of (the program's first
gradient less the reference's) over the reference's norm of that leaf or
of the median leaf, whichever is larger (``compare.leaf_gaps``'s
denominator), and of those the value the leaves are SPREAD around: the
median over the leaves. A precision too low moves every leaf, a planted
fault most of them; a token whose 22nd choice falls the other way moves
the leaves of that layer's experts and router and little else, and the
median does not follow them. The worst leaf and its name are given
beside it (``first_grad_diff_worst``, ``..._leaf``) and have no limit.
On the chip in the Nemotron-3 cell (PR 36, PERF.md section 6) the bf16
program reads 0.035 to 0.039 over twelve seeds and the 8-bit control
0.19 to 0.20, where the program's WORST leaf, an expert layer's, reads
0.17 to 0.21.

The difference is computed where both gradients are, inside the
reference (``follow_two_steps(..., against=)``), and comes with
whichever side was followed second: the reference when the program is
judged, the control or the fault when one of those is put in the
program's place.
"""

from __future__ import annotations

import numpy as np

from benchmark import compare


def leaf_differences(diff: dict, reference: dict) -> dict:
    """{leaf: the norm of the difference over the reference's norm of
    that leaf or of the median leaf} for ``diff`` and ``reference`` as
    {leaf name: [norm of each layer]}."""
    ref = compare.flat(reference)
    median = float(np.median(list(ref.values())))
    out = {}
    for name, d in compare.flat(diff).items():
        gap = d / max(ref[name], median, 1e-30)
        out[name] = gap if np.isfinite(gap) else float("inf")
    return out


def training_numbers(program: dict, reference: dict) -> dict:
    """``compare.training_numbers`` with ``first_grad_diff``."""
    out = compare.training_numbers(program, reference)
    diff = program.get("first_grad_diff", reference.get("first_grad_diff"))
    gaps = leaf_differences(diff, reference["first_grad"])
    where = max(gaps, key=gaps.get)
    out["first_grad_diff"] = float(np.median(list(gaps.values())))
    out["first_grad_diff_worst"] = gaps[where]
    out["first_grad_diff_leaf"] = where
    return out
