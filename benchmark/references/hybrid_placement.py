"""Plain reference of the batched hybrid placement solve.

From the description of Ray's hybrid scheduling policy as ray_tpu batches
it: scheduling classes are placed one after another, a later class seeing
what the earlier ones took. For one class with demand ``req`` and ``k``
pending tasks: a node is feasible if it is alive and its *total*
resources cover the demand; it can take
``min over demanded resources of available // req`` tasks; nodes are
ordered by (utilisation, truncated to 0 below the spread threshold;
not-the-local-node; slot) where utilisation is the largest
``(total - available) / total`` over resources the node has; the ``k``
tasks fill the nodes in that order up to each node's capacity.

Exact integers (fixed point as the cluster's matrix holds them) and
float64 for the utilisation. numpy only; it imports nothing of ray_tpu.
"""

from __future__ import annotations

import numpy as np


def place_class(req, k, total, available, alive, local_slot, threshold,
                skip_resource=None):
    """[nodes] int64 counts for one class. ``skip_resource`` leaves one
    resource out of the capacity: the control that breaks "no node over
    capacity"."""
    n = total.shape[0]
    demanded = [r for r in range(len(req))
                if req[r] > 0 and r != skip_resource]
    counts = np.zeros(n, dtype=np.int64)
    capacity = np.zeros(n, dtype=np.int64)
    key = []
    for node in range(n):
        feasible = bool(alive[node]) and all(
            total[node, r] >= req[r] for r in range(len(req)))
        if feasible:
            capacity[node] = min(
                (max(int(available[node, r]) // int(req[r]), 0)
                 for r in demanded), default=1 << 62)
        has = total[node] > 0
        util = float(np.max(np.where(
            has, (total[node] - available[node]) / np.maximum(total[node], 1),
            0.0))) if has.any() else 0.0
        key.append((0.0 if util < threshold else util,
                    0 if node == local_slot else 1, node))
    remaining = int(k)
    for _, _, node in sorted(key):
        if remaining <= 0:
            break
        counts[node] = min(int(capacity[node]), remaining)
        remaining -= int(counts[node])
    return counts


def place_classes(reqs, ks, total, available, alive, local_slot, threshold,
                  skip_resource=None):
    """[classes, nodes] int64 counts, classes committed in order."""
    reqs, total = np.asarray(reqs, np.int64), np.asarray(total, np.int64)
    available = np.asarray(available, np.int64).copy()
    out = np.zeros((reqs.shape[0], total.shape[0]), dtype=np.int64)
    for c in range(reqs.shape[0]):
        out[c] = place_class(reqs[c], int(ks[c]), total, available, alive,
                             local_slot, threshold, skip_resource)
        available -= out[c][:, None] * reqs[c][None, :]
    return out
