"""Scheduling policies: where does a resource request run?

Two implementations behind one seam (the reference gates policies behind
SchedulingPolicy, src/ray/raylet/scheduling/scheduling_policy.h:26):

1. ``HybridPolicy`` — an exact re-implementation of the reference's hybrid
   packing/round-robin policy (scheduling_policy.cc:39-150): skip
   infeasible nodes, prefer available ones, tie-break by critical-resource
   utilization *truncated to zero below the spread threshold* so light
   nodes compare equal and the lowest node id wins (packing); above the
   threshold the minimum-utilization node wins (spreading). Scans
   sequentially, updating availability after each placement.

2. ``BatchedHybridPolicy`` — the TPU-first path: pending requests are
   grouped by scheduling class, and each class's placement over the whole
   ``[nodes x resources]`` matrix is computed as one vectorized
   water-filling solve (feasibility mask -> per-node capacity -> ordered
   cumulative fill). One device dispatch schedules thousands of tasks.
   Verified against HybridPolicy on randomized instances in
   tests/test_scheduling_policy.py.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ray_tpu._private.config import Config
from ray_tpu.observability.metrics import scheduler_device_solves

_BIG = np.int64(2**62)


def _jit(fn, **kwargs):
    """``jax.jit`` in a process that counts its compiles by program name
    (importing device_programs registers the listener)."""
    import jax

    from ray_tpu.observability import device_programs  # noqa: F401

    return jax.jit(fn, **kwargs)


@dataclass
class SchedulingOptions:
    spread_threshold: float = 0.5
    # If set, only this node may be chosen (NodeAffinity strategy).
    node_affinity_slot: Optional[int] = None
    node_affinity_soft: bool = False
    # SPREAD strategy: ignore packing, round-robin over feasible nodes.
    spread_strategy: bool = False
    # Do not consider nodes where the request is merely feasible but not
    # currently available (used for actor creation bursts).
    require_available: bool = False

    @classmethod
    def default(cls) -> "SchedulingOptions":
        return cls(spread_threshold=Config.instance().scheduler_spread_threshold)


class HybridPolicy:
    """Exact sequential re-implementation of the reference hybrid policy."""

    def schedule_one(
        self,
        req: np.ndarray,            # [R] int64 fixed-point demand
        total: np.ndarray,          # [N, R]
        available: np.ndarray,      # [N, R]
        alive: np.ndarray,          # [N] bool
        local_slot: int,
        opts: SchedulingOptions,
    ) -> int:
        """Return the chosen node slot, or -1 if infeasible everywhere.

        Does NOT mutate availability; callers allocate on the chosen node.
        """
        n = total.shape[0]
        if n == 0:
            return -1
        if opts.node_affinity_slot is not None:
            s = opts.node_affinity_slot
            feasible = alive[s] and bool(np.all(total[s] >= req))
            if feasible:
                return s
            if not opts.node_affinity_soft:
                return -1

        feasible = alive & np.all(total >= req, axis=1)
        if not feasible.any():
            return -1
        avail_mask = feasible & np.all(available >= req, axis=1)

        # Critical-resource utilization per node *after* hypothetically
        # placing the request (reference scores on current usage;
        # scheduling_policy.cc:41-57 uses current used/total).
        with np.errstate(divide="ignore", invalid="ignore"):
            util = np.where(
                total > 0, (total - available) / np.maximum(total, 1), 0.0
            ).max(axis=1)

        if opts.spread_strategy:
            candidates = np.flatnonzero(avail_mask if avail_mask.any() else feasible)
            # Round-robin handled by the caller advancing an index; here we
            # pick min utilization then lowest id.
            order = sorted(candidates, key=lambda s: (util[s], s))
            return int(order[0])

        def best_among(mask: np.ndarray) -> int:
            slots = np.flatnonzero(mask)
            # Truncate below threshold -> ties -> prefer local, then low id
            # (reference: "prioritize local node" then node id order).
            def keyf(s):
                score = 0.0 if util[s] < opts.spread_threshold else float(util[s])
                is_local = 0 if s == local_slot else 1
                return (score, is_local, s)

            return int(min(slots, key=keyf))

        if avail_mask.any():
            return best_among(avail_mask)
        if opts.require_available:
            return -1
        return best_among(feasible)


class BatchedHybridPolicy:
    """Vectorized scheduling of a *batch* of identical-class requests.

    For one scheduling class with demand vector ``req`` and ``k`` pending
    requests, computes how many land on each node in one shot:

      capacity_n = min_r floor(available[n,r] / req[r])   (vectorized)
      order      = nodes sorted by (truncated utilization, not-local, id)
      fill       = water-filling k requests through `order` by capacity

    Returns per-node counts. The sequential policy would interleave nodes
    once all are above the spread threshold; water-filling instead fills in
    score order, which preserves the pack-below-threshold and
    spread-above-threshold structure while being one fused computation.
    """

    def __init__(self, use_jax: Optional[bool] = None):
        if use_jax is None:
            use_jax = Config.instance().scheduler_use_vectorized_policy
        self._jax_fn = None
        self._jax_fused = None
        self._jax_pipelined = None
        self.use_jax = use_jax

    # ---- numpy reference of the batched solve ---------------------------
    def schedule_class(
        self,
        req: np.ndarray,           # [R]
        k: int,
        total: np.ndarray,         # [N, R]
        available: np.ndarray,     # [N, R]
        alive: np.ndarray,         # [N]
        local_slot: int,
        opts: SchedulingOptions,
    ) -> np.ndarray:
        """Return [N] int64 counts; sum(counts) <= k (rest infeasible)."""
        n = total.shape[0]
        if n == 0 or k <= 0:
            return np.zeros(n, dtype=np.int64)
        feasible = alive & np.all(total >= req, axis=1)
        pos = req > 0
        if pos.any():
            cap = np.where(
                feasible[:, None] & pos[None, :],
                available // np.maximum(req, 1),
                _BIG,
            ).min(axis=1)
            cap = np.where(feasible, np.maximum(cap, 0), 0)
        else:
            cap = np.where(feasible, _BIG, 0)
        with np.errstate(divide="ignore", invalid="ignore"):
            util = np.where(
                total > 0, (total - available) / np.maximum(total, 1), 0.0
            ).max(axis=1)
        trunc = np.where(util < opts.spread_threshold, 0.0, util)
        not_local = (np.arange(n) != local_slot).astype(np.int64)
        order = np.lexsort((np.arange(n), not_local, trunc))
        counts = np.zeros(n, dtype=np.int64)
        remaining = k
        for s in order:
            if remaining <= 0:
                break
            take = int(min(cap[s], remaining))
            counts[s] = take
            remaining -= take
        return counts

    # ---- jax fused version ----------------------------------------------
    # The device kernel runs in float32 (TPU-native; int64 is unavailable
    # under jit without x64). Fixed-point magnitudes up to ~2^24 divide
    # exactly (_floor_div); beyond that a capacity may be off by one,
    # which the host commit loop in schedule_classes detects (allocation
    # would go negative) and repairs with the exact numpy solve for that
    # class.
    _CAP_MAX = 1.0e9

    @staticmethod
    def _floor_div(a, b):
        """floor(a / b) for float32 arrays holding integers, b >= 1:
        the host solve's ``a // b`` wherever a + b < 2^24.

        A TPU divides by a refined reciprocal, not to the last place:
        on a v5e floor(a / b) was one off on 8 of 2 000 000 random
        integer pairs, 5 of them too LOW, which no later repair sees
        (repair_oversubscription only clamps counts that are too high).
        The remainder of integers that small is exact in float32, so it
        says which way the quotient is off."""
        import jax.numpy as jnp

        q = jnp.floor(a / b)
        q = q + (a - q * b >= b)
        return q - (q * b > a)

    @staticmethod
    def _device_class_solve(req, k, total, avail, alive, perm1, threshold,
                            cap_max):
        """One scheduling class over the node matrix, on device. All f32.

        The single source of truth for the device solve — used by both the
        per-class jit (schedule_classes) and the fused whole-tick scan
        (schedule_tick_fused), so a fix to one cannot miss the other.
        req: [R]; total/avail: [N, R]; perm1: node order by (is_local, id).
        Returns counts [N] f32.
        """
        import jax.numpy as jnp

        feasible = alive & jnp.all(total >= req[None, :], axis=-1)
        pos = req > 0
        ratio = jnp.where(
            pos[None, :],
            BatchedHybridPolicy._floor_div(
                avail, jnp.maximum(req[None, :], 1.0)),
            cap_max)
        cap = jnp.min(ratio, axis=-1)
        cap = jnp.where(feasible, jnp.clip(cap, 0.0, cap_max), 0.0)
        util = jnp.max(
            jnp.where(total > 0, (total - avail)
                      / jnp.maximum(total, 1.0), 0.0), axis=-1)
        trunc = jnp.where(util < threshold, 0.0, util)
        # exact lexsort (trunc, not_local, id): stable pass over the
        # pre-sorted (not_local, id) order — matches np.lexsort in the
        # host solve bit-for-bit
        order = perm1[jnp.argsort(trunc[perm1], stable=True)]
        cap_sorted = cap[order]
        csum = jnp.cumsum(cap_sorted)
        take_sorted = jnp.clip(k - (csum - cap_sorted), 0.0, cap_sorted)
        return jnp.zeros_like(cap).at[order].set(take_sorted)

    @staticmethod
    def _perm1(n, local_slot):
        import jax.numpy as jnp

        not_local = (jnp.arange(n) != local_slot).astype(jnp.float32)
        return jnp.argsort(not_local, stable=True)

    def _build_jax(self):
        import jax

        cap_max = self._CAP_MAX
        class_solve = self._device_class_solve
        perm1_fn = self._perm1

        def solve(req, k, total, available, alive, local_slot, threshold):
            # req: [R]; total/available: [N, R] (already float32)
            perm1 = perm1_fn(total.shape[0], local_slot)
            counts = class_solve(req, k, total, available, alive, perm1,
                                 threshold, cap_max)
            return counts.astype(jax.numpy.int32)

        return _jit(solve)

    def _build_jax_fused(self):
        """Whole-tick kernel: lax.scan over scheduling classes carrying
        availability — one device dispatch schedules the entire pending
        queue. This is the bench.py north-star path."""
        import jax
        import jax.numpy as jnp

        cap_max = self._CAP_MAX
        class_solve = self._device_class_solve
        perm1_fn = self._perm1

        def tick(reqs, ks, total, available, alive, local_slot, threshold):
            # reqs: [C, R]; ks: [C]; total/available: [N, R] (float32)
            perm1 = perm1_fn(total.shape[0], local_slot)

            def one_class(avail, inputs):
                req, k = inputs
                counts = class_solve(req, k, total, avail, alive, perm1,
                                     threshold, cap_max)
                return avail - counts[:, None] * req[None, :], counts

            _, counts = jax.lax.scan(one_class, available, (reqs, ks))
            return counts.astype(jnp.int32)

        return _jit(tick)

    def _build_jax_pipelined_step(self):
        """One pipelined drain step: fold last tick's deltas into the
        DEVICE-RESIDENT availability, solve the whole tick, and
        pre-subtract this tick's usage — a single dispatch, no matrix
        re-upload. The availability buffer is DONATED: the update is
        in-place on device, so double-buffered ticks touch the host only
        for the counts pull.

        Inputs: avail [N,R] (device, donated), freed [N,R] (device —
        last tick's usage array, returned by the previous step), delta
        [N,R] (host correction upload; all-zeros and cached on device
        when the previous repair did not clamp), reqs [C,R], ks [C].
        Returns (avail', usage, counts).
        """
        import jax
        import jax.numpy as jnp

        cap_max = self._CAP_MAX
        class_solve = self._device_class_solve
        perm1_fn = self._perm1

        def step(avail, freed, delta, reqs, ks, total, alive, local_slot,
                 threshold):
            avail = avail + freed + delta
            perm1 = perm1_fn(total.shape[0], local_slot)

            def one_class(acc, inputs):
                req, k = inputs
                counts = class_solve(req, k, total, acc, alive, perm1,
                                     threshold, cap_max)
                return acc - counts[:, None] * req[None, :], counts

            _, counts = jax.lax.scan(one_class, avail, (reqs, ks))
            usage = jnp.einsum("cn,cr->nr", counts, reqs)
            return avail - usage, usage, counts.astype(jnp.int32)

        return _jit(step, donate_argnums=(0,))

    def pipelined_step(self, avail_dev, freed_dev, delta_dev, reqs, ks,
                       total_dev, alive_dev, local_slot: int,
                       opts: SchedulingOptions):
        """Dispatch one double-buffered drain step asynchronously.
        Returns (avail', usage, counts) device arrays WITHOUT blocking —
        the caller overlaps host commit of the previous tick with this
        solve and only syncs on the counts pull. ``avail_dev`` is
        donated (consumed); use the returned availability."""
        if self._jax_pipelined is None:
            self._jax_pipelined = self._build_jax_pipelined_step()
        reqs, ks = self._to_f32(reqs, ks)
        return self._jax_pipelined(avail_dev, freed_dev, delta_dev, reqs,
                                   ks, total_dev, alive_dev, local_slot,
                                   opts.spread_threshold)

    @staticmethod
    def _to_f32(*arrays):
        """Host-side float32 coercion BEFORE device transfer: int64
        fixed-point above 2^31 would wrap negative if jax truncated it to
        int32 (x64 off), making feasible nodes look infeasible. float32
        keeps the magnitude (approximately); capacity off-by-ones from
        rounding are repaired by the caller's exact-host fallback."""
        import jax.numpy as jnp

        out = []
        for a in arrays:
            if isinstance(a, np.ndarray) and a.dtype != np.float32:
                out.append(np.asarray(a, dtype=np.float32))
            elif hasattr(a, "dtype") and a.dtype not in (np.float32, bool):
                out.append(jnp.asarray(a, dtype=jnp.float32))
            else:
                out.append(a)
        return out

    def schedule_tick_fused(self, reqs, ks, total, available, alive,
                            local_slot: int, opts: SchedulingOptions):
        """One-dispatch whole-queue schedule; returns a device array
        [C, N]. Callers must pass the pulled counts through
        ``repair_oversubscription`` before committing them — the device
        solve runs in float32, and magnitudes above 2^24 can round a
        capacity up by one."""
        if self._jax_fused is None:
            self._jax_fused = self._build_jax_fused()
        reqs, ks, total, available = self._to_f32(reqs, ks, total, available)
        counts = self._jax_fused(reqs, ks, total, available, alive,
                                 local_slot, opts.spread_threshold)
        scheduler_device_solves.inc(
            tags={"platform": next(iter(counts.devices())).platform})
        return counts

    @staticmethod
    def repair_oversubscription(reqs: np.ndarray, counts: np.ndarray,
                                available: np.ndarray) -> np.ndarray:
        """Exact int64 host pass over fused-tick output: clamp each
        class's per-node count to the capacity actually left after the
        preceding classes committed.

        Fast path: if the WHOLE batch fits (``available - total_usage >=
        0`` everywhere), no class can be over capacity after its
        predecessors either — usage is non-negative, so every prefix sum
        is bounded by the total — and the per-class clamp loop is
        skipped. The loop only runs on an actual f32 capacity
        off-by-one, which needs fixed-point magnitudes near 2^24."""
        counts = np.asarray(counts, dtype=np.int64)
        reqs = np.asarray(reqs, dtype=np.int64)
        avail = np.asarray(available, dtype=np.int64)
        usage = counts.T @ reqs                 # [N, R] int64, exact
        if np.all(avail >= usage):
            return counts.copy()
        counts = counts.copy()
        avail = avail.copy()
        for c in range(counts.shape[0]):
            req = reqs[c]                      # [R]
            pos = req > 0
            if pos.any():
                # [N]: exact max placements per node for this class
                cap = np.min(avail[:, pos] // req[pos], axis=1)
                cap = np.maximum(cap, 0)
                counts[c] = np.minimum(counts[c], cap)
            avail -= counts[c][:, None] * req[None, :]
        return counts

    def schedule_classes(
        self,
        reqs: np.ndarray,          # [C, R]
        ks: np.ndarray,            # [C]
        total: np.ndarray,
        available: np.ndarray,
        alive: np.ndarray,
        local_slot: int,
        opts: SchedulingOptions,
    ) -> np.ndarray:
        """Schedule C scheduling classes at once -> [C, N] counts.

        Classes are committed in order; a later class sees availability
        reduced by earlier classes' placements (host-side fixup loop kept
        cheap because C is small in practice).
        """
        if self.use_jax:
            if self._jax_fn is None:
                self._jax_fn = self._build_jax()
            out = np.zeros((reqs.shape[0], total.shape[0]), dtype=np.int64)
            avail = available.copy()
            # One device solve per class against committed availability —
            # exact parity with the sequential path. The node axis (the
            # large one: 100k-task queues collapse into few classes over
            # many nodes) stays fully vectorized on device.
            total_f, = self._to_f32(total)
            for c in range(reqs.shape[0]):
                req_f, k_f, avail_f = self._to_f32(
                    reqs[c], np.float32(ks[c]), avail)
                counts = np.asarray(
                    self._jax_fn(req_f, k_f, total_f, avail_f, alive,
                                 local_slot, opts.spread_threshold)
                ).astype(np.int64)
                used = counts[:, None] * reqs[c][None, :]
                if np.any((avail - used) < 0):
                    # float32 capacity off-by-one on huge magnitudes:
                    # repair with the exact host solve
                    counts = self.schedule_class(
                        reqs[c], int(ks[c]), total, avail, alive,
                        local_slot, opts)
                    used = counts[:, None] * reqs[c][None, :]
                avail = avail - used
                out[c] = counts
            return out
        out = np.zeros((reqs.shape[0], total.shape[0]), dtype=np.int64)
        avail = available.copy()
        for c in range(reqs.shape[0]):
            counts = self.schedule_class(
                reqs[c], int(ks[c]), total, avail, alive, local_slot, opts)
            avail = avail - counts[:, None] * reqs[c][None, :]
            out[c] = counts
        return out


class DeviceMatrixMirror:
    """Device-resident ``total/available/alive`` mirror of a host
    :class:`~ray_tpu.scheduler.resources.ResourceMatrix`.

    The pipelined scheduler tick solves against these buffers instead of
    re-coercing and re-uploading the full ``[nodes x resources]`` matrix
    every tick (ROADMAP Open item 2: the upload was pure host time
    between device solves). Freshness protocol:

      - a ``matrix.version`` jump (new node, wider resource axis,
        liveness flip) forces a FULL re-sync;
      - otherwise only the rows ``matrix.consume_dirty_rows()`` reports
        (commit/heartbeat deltas) are folded in by one small jitted
        scatter with a DONATED destination buffer — an in-place device
        update, bytes proportional to changed rows;
      - every ``sync_period`` delta refreshes a full re-sync runs anyway
        so numerical drift (f32 folding of >2^24 fixed-point rows)
        cannot accumulate;
      - ``debug_check`` compares the folded device availability against
        the host matrix elementwise after every refresh and raises on
        the first divergence (the drift guard for development and the
        scheduler_pipeline test marker).

    Synchronization: callers hold the cluster lock while calling
    ``refresh`` (it reads the host matrix), and must NOT hold it while
    blocking on device results. The returned arrays are functionally
    immutable; using them after the lock is released is safe.
    """

    def __init__(self):
        self._version: Optional[int] = None
        self._total = None
        self._avail = None
        self._alive = None
        self._refreshes_since_full = 0
        self._set_rows_fn = None
        # observability: bench.py reports upload bytes per tick off/on
        self.upload_bytes_total = 0
        self.full_syncs = 0
        self.delta_syncs = 0

    @staticmethod
    def _build_set_rows():
        import jax

        def set_rows(total, avail, idx, rows_t, rows_a):
            return total.at[idx].set(rows_t), avail.at[idx].set(rows_a)

        return jax.jit(set_rows, donate_argnums=(0, 1))

    def refresh(self, matrix, sync_period: int,
                debug_check: bool = False) -> Tuple:
        """Bring the mirror up to date with the host matrix; returns
        ``(total, available, alive, uploaded_bytes)`` device arrays in
        the solve's f32/bool layout. Caller holds the cluster lock."""
        import jax

        full = (self._total is None
                or self._version != matrix.version
                or self._refreshes_since_full >= max(1, int(sync_period)))
        if full:
            self._total = jax.device_put(
                np.asarray(matrix.total, dtype=np.float32))
            self._avail = jax.device_put(
                np.asarray(matrix.available, dtype=np.float32))
            self._alive = jax.device_put(np.asarray(matrix.alive))
            matrix.consume_dirty_rows()  # subsumed by the full upload
            self._version = matrix.version
            self._refreshes_since_full = 0
            self.full_syncs += 1
            uploaded = (self._total.nbytes + self._avail.nbytes
                        + self._alive.nbytes)
        else:
            self._refreshes_since_full += 1
            idx = matrix.consume_dirty_rows()
            uploaded = 0
            if idx.size:
                # pad the row set to a power-of-two bucket (repeating the
                # last row — scatter-set is idempotent for identical
                # rows) so the jitted scatter compiles per bucket, not
                # per distinct dirty-count
                bucket = 1 << int(idx.size - 1).bit_length()
                if bucket > idx.size:
                    idx = np.concatenate(
                        [idx, np.repeat(idx[-1:], bucket - idx.size)])
                idx = idx.astype(np.int32)
                rows_t = np.asarray(matrix.total[idx], dtype=np.float32)
                rows_a = np.asarray(matrix.available[idx],
                                    dtype=np.float32)
                if self._set_rows_fn is None:
                    self._set_rows_fn = self._build_set_rows()
                self._total, self._avail = self._set_rows_fn(
                    self._total, self._avail, idx, rows_t, rows_a)
                self.delta_syncs += 1
                uploaded = rows_t.nbytes + rows_a.nbytes + idx.nbytes
        self.upload_bytes_total += uploaded
        if debug_check:
            host_a = np.asarray(matrix.available, dtype=np.float32)
            dev_a = np.asarray(self._avail)
            if not np.array_equal(host_a, dev_a):
                bad = int((host_a != dev_a).sum())
                raise AssertionError(
                    f"device matrix mirror drifted from host on {bad} "
                    f"cell(s) (version={matrix.version}, "
                    f"since_full={self._refreshes_since_full})")
        return self._total, self._avail, self._alive, uploaded


_shared_policies: Dict[bool, BatchedHybridPolicy] = {}


def shared_batched_policy(use_jax: bool) -> BatchedHybridPolicy:
    """Process-wide shared instance per backend flavor. The jit caches
    live on the instance; in-process clusters run hundreds of raylets in
    one interpreter, and per-raylet instances would recompile the same
    fused tick kernel hundreds of times."""
    policy = _shared_policies.get(use_jax)
    if policy is None:
        policy = _shared_policies.setdefault(
            use_jax, BatchedHybridPolicy(use_jax=use_jax))
    return policy
