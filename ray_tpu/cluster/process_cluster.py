"""Process-separated cluster: real OS processes per role, driven by tests.

``ProcessCluster`` mirrors the reference's multi-node-on-one-host test rig
(python/ray/cluster_utils.py:101 Cluster.add_node:170/remove_node:244 and
_private/services.py:1566 start_raylet): it spawns one GCS server process
and one raylet server process per node, and can SIGKILL any of them — a
*real* node death, detected by the GCS heartbeat manager, not a method
call.

``ClusterClient`` is the driver: it submits tasks to raylet processes
(spillback-retrying across nodes), keeps the lineage needed to resubmit
work lost to node death (reference: TaskManager::ResubmitTask), proxies
actor calls to the actor's current node with re-resolution on restart,
and fetches results over the chunked object-transfer plane.
"""

from __future__ import annotations

import logging
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu.cluster import overload as _overload
from ray_tpu.cluster import protocol
from ray_tpu.cluster.rpc import RpcClient, RpcConnectionError
from ray_tpu.exceptions import (
    ActorDiedError,
    GetTimeoutError,
    RayActorError,
    RetryLaterError,
    WorkerCrashedError,
)

logger = logging.getLogger(__name__)


def _spawn(args: List[str], scrape: str, timeout: float = 30.0,
           extra_env: Optional[Dict[str, str]] = None
           ) -> Tuple[subprocess.Popen, List[str]]:
    """Start a server process and scrape its announce line from stdout."""
    # raylet/GCS server processes never own the chip
    # (cluster/child_env.py — shared with the worker pools and the
    # command provider)
    from ray_tpu.cluster.child_env import child_env

    env = child_env()
    if extra_env:
        # per-process overrides: fault-injection plans
        # (RAY_TPU_FAULT_PLAN, cluster/fault_plane.py) and config flags
        env.update(extra_env)
    proc = subprocess.Popen(
        [sys.executable, "-m"] + args, stdout=subprocess.PIPE,
        stderr=None, env=env, text=True)
    deadline = time.monotonic() + timeout
    line = ""
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            raise RuntimeError(
                f"{args[0]} exited during startup "
                f"(rc={proc.poll()})")
        if line.startswith(scrape):
            return proc, line.split()
    raise RuntimeError(f"{args[0]} did not announce within {timeout}s")


class ProcessCluster:
    """Spawns and kills the cluster's real processes."""

    def __init__(self, heartbeat_period_ms: int = 50,
                 num_heartbeats_timeout: int = 10,
                 storage_path: str = "",
                 gcs_env: Optional[Dict[str, str]] = None):
        self._gcs_args = [
            "--heartbeat-period-ms", str(heartbeat_period_ms),
            "--num-heartbeats-timeout", str(num_heartbeats_timeout)]
        if storage_path:
            self._gcs_args += ["--storage", storage_path]
        self._gcs_env = dict(gcs_env or {})
        self.gcs_proc, fields = _spawn(
            ["ray_tpu.cluster.gcs_server"] + self._gcs_args,
            "GCS_ADDRESS", extra_env=self._gcs_env)
        self.gcs_address = fields[1]
        self.raylets: Dict[str, subprocess.Popen] = {}  # node_id -> proc
        self.node_addresses: Dict[str, str] = {}

    def restart_gcs(self, env: Optional[Dict[str, str]] = None) -> None:
        """Bring the GCS back on the SAME address after a kill — the
        reference's GCS fault-tolerance scenario (tests/
        test_gcs_fault_tolerance.py): raylets keep running, heartbeats
        re-register, state reloads from table storage. ``env`` replaces
        the GCS's extra environment for the new incarnation (pass ``{}``
        to shed a fault plan the old incarnation ran under)."""
        if self.gcs_proc.poll() is None:
            self.kill_gcs()
        if env is not None:
            self._gcs_env = dict(env)
        port = self.gcs_address.rsplit(":", 1)[1]
        self.gcs_proc, fields = _spawn(
            ["ray_tpu.cluster.gcs_server", "--port", port]
            + self._gcs_args, "GCS_ADDRESS", timeout=60.0,
            extra_env=self._gcs_env)
        assert fields[1] == self.gcs_address, (fields, self.gcs_address)

    def add_node(self, num_cpus: float = 2,
                 resources: Optional[Dict[str, float]] = None,
                 num_workers: Optional[int] = None,
                 object_store_memory: Optional[int] = None,
                 extra_env: Optional[Dict[str, str]] = None) -> str:
        import json

        node_resources = dict(resources or {})
        node_resources.setdefault("CPU", float(num_cpus))
        args = ["ray_tpu.cluster.raylet_server", "--gcs", self.gcs_address,
                "--resources", json.dumps(node_resources),
                "--num-workers", str(num_workers or max(1, int(num_cpus)))]
        if object_store_memory:
            args += ["--object-store-memory", str(object_store_memory)]
        proc, fields = _spawn(args, "RAYLET_ADDRESS", timeout=60.0,
                              extra_env=extra_env)
        address, node_id = fields[1], fields[3]
        self.raylets[node_id] = proc
        self.node_addresses[node_id] = address
        return node_id

    def node_stats(self, node_id: str) -> dict:
        client = RpcClient(self.node_addresses[node_id])
        try:
            return client.call("node_stats", timeout=10.0)
        finally:
            client.close()

    def preempt_node(self, node_id: str, notice_s: float = 2.0,
                     reason: str = "preempted") -> dict:
        """Deliver a spot-provider preemption notice to a raylet: the
        node reports it on its next heartbeat and the GCS drains it
        inside the window. The eviction itself (kill_node after
        notice_s) is the caller's job — providers never promise the
        drain finishes first."""
        client = RpcClient(self.node_addresses[node_id])
        try:
            return client.call("preempt_notice", notice_s=float(notice_s),
                               reason=reason, timeout=10.0)
        finally:
            client.close()

    def kill_node(self, node_id: str, sig: int = signal.SIGKILL) -> None:
        """Hard-kill a raylet process — node death as the OS sees it."""
        proc = self.raylets.pop(node_id, None)
        if proc is None:
            raise KeyError(f"unknown node {node_id}")
        proc.send_signal(sig)
        proc.wait(timeout=10)

    def remove_node(self, node_id: str) -> None:
        """Graceful scale-down: drain through the GCS first (so actors /
        PGs reschedule off the node), then stop the process (reference:
        `ray stop` on a worker node → NodeManager drain)."""
        try:
            client = RpcClient(self.gcs_address)
            try:
                client.call("drain_node", node_id=node_id, timeout=15.0)
            finally:
                client.close()
        except Exception as e:
            # GCS gone: fall through to process termination
            logger.debug("graceful drain of node %s failed: %r",
                         node_id[:8], e)
        proc = self.raylets.pop(node_id, None)
        if proc is None:
            return
        proc.terminate()
        try:
            proc.wait(timeout=5)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=5)

    def kill_gcs(self, sig: int = signal.SIGKILL) -> None:
        self.gcs_proc.send_signal(sig)
        self.gcs_proc.wait(timeout=10)

    def wait_for_nodes(self, count: int, timeout: float = 30.0) -> None:
        client = RpcClient(self.gcs_address)
        try:
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                view = client.call("cluster_view", timeout=10.0)
                alive = [n for n in view["nodes"].values() if n["alive"]]
                if len(alive) >= count:
                    return
                time.sleep(0.05)
            raise TimeoutError(
                f"only {len(alive)} nodes alive after {timeout}s")
        finally:
            client.close()

    def shutdown(self) -> None:
        for proc in self.raylets.values():
            try:
                proc.kill()
                proc.wait(timeout=5)
            except Exception as e:
                logger.debug("raylet pid %s kill failed: %r",
                             getattr(proc, "pid", "?"), e)
        self.raylets.clear()
        try:
            self.gcs_proc.kill()
            self.gcs_proc.wait(timeout=5)
        except Exception as e:
            logger.debug("gcs pid %s kill failed: %r",
                         getattr(self.gcs_proc, "pid", "?"), e)


class ClusterRef:
    """Driver-side handle to an object produced in the cluster."""

    __slots__ = ("object_id", "task_id", "node_id")

    def __init__(self, object_id: bytes, task_id: str = "",
                 node_id: str = ""):
        self.object_id = object_id
        self.task_id = task_id
        self.node_id = node_id  # node the producing task was sent to

    def hex(self) -> str:
        return self.object_id.hex()

    def __repr__(self):
        return f"ClusterRef({self.object_id.hex()[:12]})"


class ClusterActorHandle:
    __slots__ = ("_client", "actor_id")

    def __init__(self, client: "ClusterClient", actor_id: str):
        self._client = client
        self.actor_id = actor_id

    def __getattr__(self, name: str):
        if name.startswith("_"):
            raise AttributeError(name)
        client = self._client
        actor_id = self.actor_id

        def _call(*args, **kwargs):
            return client._actor_call(actor_id, name, args, kwargs)

        _call.__name__ = name
        return _call


class _ActorBatcher:
    """Client-side submit coalescer for the batched actor-lifecycle
    RPCs: concurrent ``create_actor``/``kill_actor`` callers enqueue
    rows, the first submitter becomes the drainer and flushes up to
    ``actor_batch_max`` rows per ``actor_create_batch`` /
    ``actor_kill_batch`` frame after an ``actor_batch_linger_s`` linger
    (long enough for a burst to pile up, short enough to be invisible
    on a lone call). One request token per flushed frame; per-row
    results fan back to their callers through events."""

    def __init__(self, name: str, flush_fn, linger_s: float,
                 max_batch: int):
        self._name = name
        # flush_fn(rows) -> {"results": [row, ...]} — owns the wire
        # call (and its request token) so the RPC site stays a literal
        # the wire-conformance checker can join against the schema
        self._flush_fn = flush_fn
        self._linger_s = linger_s
        self._max = max(1, max_batch)
        self._lock = threading.Lock()
        self._queue: List[Tuple[dict, dict]] = []
        self._draining = False

    def submit(self, row: dict, timeout: float = 120.0) -> dict:
        slot: Dict[str, Any] = {"event": threading.Event(),
                                "result": None, "error": None}
        with self._lock:
            self._queue.append((row, slot))
            leader = not self._draining
            if leader:
                self._draining = True
        if leader:
            self._drain()
        if not slot["event"].wait(timeout):
            raise GetTimeoutError(
                f"batched {self._name} row did not complete "
                f"within {timeout}s")
        if slot["error"] is not None:
            raise slot["error"]
        return slot["result"]

    def _drain(self) -> None:
        try:
            while True:
                time.sleep(self._linger_s)  # let the burst accumulate
                with self._lock:
                    batch = self._queue[:self._max]
                    del self._queue[:self._max]
                    if not batch:
                        self._draining = False
                        return
                rows = [r for r, _ in batch]
                try:
                    reply = self._flush_fn(rows)
                    for (_, slot), res in zip(batch, reply["results"]):
                        slot["result"] = res
                        slot["event"].set()
                except BaseException as e:  # noqa: BLE001
                    # frame-level failure: every row in it fails typed
                    for _, slot in batch:
                        slot["error"] = e
                        slot["event"].set()
        except BaseException:
            # the drainer must never die with followers still parked
            with self._lock:
                orphans = self._queue[:]
                self._queue.clear()
                self._draining = False
            for _, slot in orphans:
                slot["error"] = RuntimeError(
                    f"{self._name} batcher drain failed")
                slot["event"].set()
            raise


def _binomial_plan(nodes: List[str], addr_of: Dict[str, str]) -> list:
    """Binomial chunk-tree plan: the first pending node becomes a
    child and takes (half - 1) of the remainder as ITS subtree; depth
    is ceil(log2(N+1)) and every interior node forwards while it still
    receives. Returns ``[[address, subtree], ...]``."""
    out: list = []
    while nodes:
        half = (len(nodes) + 1) // 2
        head, sub, nodes = nodes[0], nodes[1:half], nodes[half:]
        out.append([addr_of[head], _binomial_plan(sub, addr_of)])
    return out


def _chain_plan(nodes: List[str], addr_of: Dict[str, str]) -> list:
    """Single-successor chain: depth N, fan-out 1 at every hop — the
    max-depth stress shape for cut-through forwarding."""
    plan: list = []
    for nid in reversed(nodes):
        plan = [[addr_of[nid], plan]]
    return plan


def _plan_depth(plan: list) -> int:
    if not plan:
        return 0
    return 1 + max(_plan_depth(sub) for _, sub in plan)


class ClusterClient:
    """The driver process's connection to a ProcessCluster."""

    # plan of the most recent broadcast() (topology/depth/fanout) —
    # bench and tests read it; None until the first broadcast
    last_broadcast_plan: Optional[Dict[str, Any]] = None

    def __init__(self, gcs_address: str):
        self.gcs_address = gcs_address
        from collections import OrderedDict

        from ray_tpu._private.config import Config
        from ray_tpu.cluster.rpc import ReconnectingRpcClient

        self.gcs = ReconnectingRpcClient(gcs_address)
        self._raylet_clients: Dict[str, RpcClient] = {}  # address -> client
        # return_id -> task spec, kept for node-death resubmission;
        # LRU-bounded like the in-process runtime's lineage cache
        self._lineage: "OrderedDict[bytes, dict]" = OrderedDict()
        self._retries: Dict[bytes, int] = {}
        self._lineage_cap = 10_000
        self._lock = threading.Lock()
        self._counter = 0
        cfg = Config.instance()
        # master switch: with worker_pool_enabled off, create/kill take
        # the exact pre-batching serial RPCs (one frame per actor)
        self._batching = cfg.worker_pool_enabled
        self._create_batcher = _ActorBatcher(
            "actor_create_batch",
            lambda rows: self.gcs.call(
                "actor_create_batch", creates=rows,
                token=self._next_id("tok"), timeout=120.0),
            cfg.actor_batch_linger_s, cfg.actor_batch_max)
        self._kill_batcher = _ActorBatcher(
            "actor_kill_batch",
            lambda rows: self.gcs.call(
                "actor_kill_batch", kills=rows,
                token=self._next_id("tok"), timeout=120.0),
            cfg.actor_batch_linger_s, cfg.actor_batch_max)
        # ---- dispatch fast lane (driver side) ----
        # master switch: off restores the exact serial submit_task RPC,
        # per-submit func pickling, and always-inline args
        self._fastlane = cfg.dispatch_fastlane_enabled
        self._submit_linger_s = cfg.dispatch_batch_linger_s
        self._submit_batch_max = cfg.dispatch_batch_max
        self._inline_arg_max = (cfg.dispatch_inline_arg_max
                                if cfg.dispatch_inline_arg_max > 0
                                else cfg.max_direct_call_object_size)
        # one submit coalescer per raylet address (created lazily: the
        # flush target is the node the spec was routed to)
        self._submit_batchers: Dict[str, _ActorBatcher] = {}
        # func -> pickled bytes: the template memo for this tier — a
        # hot loop resubmitting the same function re-encodes only args
        # and ids, not the closure (bounded; unhashable funcs skip it)
        self._func_bytes: Dict[Any, bytes] = {}
        # node_id -> monotonic deadline: a raylet whose connection just
        # failed is SUSPECT until the deadline. The GCS needs a full
        # heartbeat-timeout window to declare it dead, and until then
        # the node looks maximally free (its availability never drains)
        # — so without this hint every placement decision piles onto
        # the corpse, and under a concurrent workload the whole driver
        # stalls until the verdict. A suspect node is only deprioritized
        # (it still takes work when it is the only feasible node), so a
        # transient conn blip costs a few seconds of avoidance, never
        # livelock.
        self._suspect_until: Dict[str, float] = {}

    # ------------------------------------------------------------ plumbing
    def _next_id(self, prefix: str) -> str:
        with self._lock:
            self._counter += 1
            return f"{prefix}-{os.getpid()}-{self._counter:08d}"

    def _raylet(self, address: str) -> RpcClient:
        c = self._raylet_clients.get(address)
        if c is None or c.closed:
            c = RpcClient(address)
            self._raylet_clients[address] = c
        return c

    def _submit_batcher(self, address: str) -> _ActorBatcher:
        """The per-raylet submit coalescer (dispatch fast lane):
        concurrent ``_submit_spec`` callers routed to the same node
        pile their specs onto one ``submit_task_batch`` frame; per-row
        accept/backpressure results fan back through the batcher."""
        with self._lock:
            b = self._submit_batchers.get(address)
            if b is None:
                b = _ActorBatcher(
                    "submit_task_batch",
                    lambda rows, _a=address: self._raylet(_a).call(
                        "submit_task_batch", specs=rows, timeout=30.0),
                    self._submit_linger_s, self._submit_batch_max)
                self._submit_batchers[address] = b
            return b

    def _dumps_func(self, func) -> bytes:
        """Pickle a task function, memoized per function object on the
        fast lane — resubmitting the same function skips cloudpickle
        entirely (the closure was frozen at first submit, the
        template contract)."""
        if self._fastlane:
            try:
                data = self._func_bytes.get(func)
            except TypeError:  # unhashable callable
                return protocol.dumps(func)
            if data is None:
                data = protocol.dumps(func)
                if len(self._func_bytes) < 4096:
                    self._func_bytes[func] = data
            return data
        return protocol.dumps(func)

    def cluster_view(self) -> dict:
        return self.gcs.call("cluster_view", timeout=10.0)

    def subscriber(self, poll_timeout_s: float = 5.0):
        """A Subscriber over the GCS-hosted pubsub channels (ACTOR, NODE,
        OBJECT_LOCATION, LOG, ERROR). Caller owns close()."""
        from ray_tpu.pubsub import Subscriber

        sid = self._next_id("sub")
        return Subscriber(
            sid,
            poll_fn=lambda subscriber_id, timeout: self.gcs.call(
                "pubsub_poll", subscriber_id=subscriber_id,
                timeout_s=timeout, timeout=timeout + 10.0),
            subscribe_fn=lambda **kw: self.gcs.call(
                "pubsub_subscribe", timeout=10.0, **kw),
            unsubscribe_fn=lambda **kw: self.gcs.call(
                "pubsub_unsubscribe", timeout=10.0, **kw),
            poll_timeout_s=poll_timeout_s,
        )

    def _alive_nodes(self) -> List[Tuple[str, dict]]:
        view = self.cluster_view()
        return [(nid, info) for nid, info in view["nodes"].items()
                if info["alive"]]

    def _mark_suspect(self, node_id: str, ttl_s: float = 3.0) -> None:
        """Steer placement away from a conn-failed raylet for ttl_s —
        long enough to bridge the gap until the GCS's heartbeat verdict
        lands, short enough that a false alarm self-heals."""
        with self._lock:
            self._suspect_until[node_id] = time.monotonic() + ttl_s

    def _clear_suspect(self, node_id: str) -> None:
        """A successful dispatch is proof of life: drop the suspicion
        early instead of waiting out the TTL, so a reconnected node
        regains full placement eligibility on its first accepted
        frame."""
        with self._lock:
            self._suspect_until.pop(node_id, None)

    def _is_suspect(self, node_id: str) -> bool:
        with self._lock:
            deadline = self._suspect_until.get(node_id)
            if deadline is None:
                return False
            if deadline <= time.monotonic():
                del self._suspect_until[node_id]
                return False
            return True

    def _pick_node(self, resources: Dict[str, float],
                   exclude: Optional[set] = None) -> Optional[Tuple[str, dict]]:
        """Most-available feasible node (driver-side lease targeting;
        reference lease_policy.cc picks by locality, we pick by headroom).
        Suspect nodes (recent conn failure, no death verdict yet) lose
        to any non-suspect candidate but stay eligible as a last
        resort."""
        exclude = exclude or set()
        best = None
        best_score = None
        for nid, info in self._alive_nodes():
            if nid in exclude:
                continue
            if any(info["resources"].get(k, 0.0) < v
                   for k, v in resources.items()):
                continue
            avail = info["available"]
            score = sum(avail.values())
            if any(avail.get(k, 0.0) < v for k, v in resources.items()):
                score -= 1e6  # feasible-but-busy: allowed, deprioritized
            if self._is_suspect(nid):
                score -= 1e9  # likely dead: below every healthy option
            if info.get("state") == "DRAINING":
                score -= 1e9  # leaving soon: below every healthy option
            if best_score is None or score > best_score:
                best, best_score = (nid, info), score
        return best

    # ---------------------------------------------------------------- tasks
    def submit(self, func, args: tuple = (), kwargs: Optional[dict] = None,
               resources: Optional[Dict[str, float]] = None,
               max_retries: int = 3, node_id: Optional[str] = None,
               runtime_env: Optional[dict] = None) -> ClusterRef:
        task_id = self._next_id("task")
        return_id = os.urandom(28)
        spec = {
            "task_id": task_id,
            "func": self._dumps_func(func),
            "args": [self._pack_arg(a) for a in args],
            "kwargs": {k: self._pack_arg(v)
                       for k, v in (kwargs or {}).items()},
            "resources": dict(resources or {"CPU": 1.0}),
            "return_id": return_id,
        }
        if runtime_env is not None:
            # normalize driver-side: pip/conda envs materialize here,
            # py_modules dirs package into pymod:// URIs seeded to THIS
            # tier's KV (the GCS server) — the raylet's
            # _stage_py_modules fetches from the same store, so remote
            # nodes without the archive can resolve it
            from ray_tpu._private.runtime_env import normalize
            from ray_tpu._private.runtime_env_packaging import (
                KV_NAMESPACE,
            )

            spec["runtime_env"] = normalize(
                runtime_env,
                kv_put=lambda k, v: self.kv_put(k, v, ns=KV_NAMESPACE))
        # observability plane: a sampled trace rides inside the spec, so
        # the raylet's execution span parents to the driver's current
        # span across the wire (reference: tracing_helper.py carrying
        # context in the task spec)
        from ray_tpu.util import tracing as _tracing
        if _tracing.enabled():
            ctx = _tracing.current_context()
            if ctx is not None and ctx.sampled:
                spec["trace_context"] = ctx.to_dict()
        assigned = self._submit_spec(spec, node_hint=node_id)
        ref = ClusterRef(return_id, task_id, assigned)
        with self._lock:
            self._lineage[return_id] = spec
            while len(self._lineage) > self._lineage_cap:
                old, _ = self._lineage.popitem(last=False)
                self._retries.pop(old, None)
            self._retries[return_id] = max_retries
        return ref

    def _pack_arg(self, value) -> tuple:
        if isinstance(value, ClusterRef):
            return ("ref", value.object_id)
        data = protocol.dumps(value)
        if self._fastlane and len(data) > self._inline_arg_max:
            # out-of-band handoff (dispatch fast lane): an oversized
            # arg is stored ONCE through the object plane — the
            # executing node resolves it over the shm fast path — so
            # the submit frame stays small instead of carrying the
            # payload on every wire hop
            return ("ref", self.put(value).object_id)
        return ("v", data)

    def _submit_spec(self, spec: dict, node_hint: Optional[str] = None,
                     exclude: Optional[set] = None) -> str:
        """Send to a raylet; on rejection/conn-failure spill to the next
        node (grant-or-reject spillback, direct_task_transport.cc:295).
        A RetryLaterError is BACKPRESSURE, not rejection: the node is
        healthy but its bounded queue is full — sleep the hinted pace
        and offer the task again (possibly to a less loaded node)
        without excluding the pushing-back node."""
        exclude = set(exclude or ())
        hint = node_hint
        backpressure_deadline = time.monotonic() + 120.0
        attempts = 0
        while attempts < 8:
            target = None
            if hint and hint not in exclude:
                for nid, info in self._alive_nodes():
                    if nid == hint:
                        target = (nid, info)
                        break
                hint = None
            if target is None:
                target = self._pick_node(spec["resources"], exclude)
            if target is None:
                attempts += 1
                time.sleep(0.2)
                continue
            nid, info = target
            try:
                if self._fastlane and _overload.lane_enabled("dispatch"):
                    # fast lane: the spec rides a coalesced
                    # submit_task_batch frame with every other submit
                    # routed to this node in the linger window; the
                    # per-row reply mirrors the serial RPC's. The row
                    # token is stamped once and survives every retry of
                    # this spec (this dict is the retried object), so a
                    # frame replayed after a dropped reply dedupes on
                    # the raylet instead of double-queueing the task.
                    if not spec.get("token"):
                        spec["token"] = self._next_id("rowtok")
                    try:
                        reply = self._submit_batcher(
                            info["address"]).submit(spec, timeout=40.0)
                    except RetryLaterError:
                        # a shed is load pushback, not a lane defect:
                        # the frame round-tripped fine
                        _overload.lane_ok("dispatch")
                        raise
                    except BaseException:
                        _overload.lane_failed("dispatch")
                        raise
                    _overload.lane_ok("dispatch")
                else:
                    # serial safe path: operator switch off, or the
                    # dispatch lane breaker is open (degraded mode)
                    reply = self._raylet(info["address"]).call(
                        "submit_task", spec=spec, timeout=30.0)
            except RetryLaterError as e:
                if time.monotonic() >= backpressure_deadline:
                    raise
                time.sleep(e.retry_after_s)
                continue  # same node stays eligible; no attempt burned
            except (RpcConnectionError, TimeoutError):
                # remember the failure beyond this one task: until the
                # heartbeat verdict, the dead node looks maximally free
                # and would win every subsequent _pick_node
                self._mark_suspect(nid)
                attempts += 1
                exclude.add(nid)
                continue
            if reply.get("accepted"):
                # the last-resort pick answered after all: reconnected,
                # not dead — restore full eligibility immediately
                self._clear_suspect(nid)
                return nid
            if reply.get("reason") == "backpressure":
                # per-row backpressure from a batched frame: the
                # RetryLaterError semantics ride the row — same node
                # stays eligible, no attempt burned, hinted pace
                if time.monotonic() >= backpressure_deadline:
                    raise RetryLaterError(
                        f"node {nid[:8]} kept shedding submits for "
                        f"task {spec['task_id']}",
                        retry_after_s=reply.get("retry_after_s", 0.1))
                time.sleep(reply.get("retry_after_s", 0.05))
                continue
            attempts += 1
            exclude.add(nid)
        raise RuntimeError(
            f"no node accepted task {spec['task_id']} "
            f"(demand={spec['resources']})")

    def _resubmit(self, ref: ClusterRef) -> bool:
        """Lineage resubmission after node death (TaskManager::
        ResubmitTask, task_manager.cc:99)."""
        with self._lock:
            spec = self._lineage.get(ref.object_id)
            left = self._retries.get(ref.object_id, 0)
            if spec is None or left <= 0:
                return False
            self._retries[ref.object_id] = left - 1
        logger.warning("resubmitting task %s after node loss (%d retries "
                       "left)", spec["task_id"][:12], left - 1)
        ref.node_id = self._submit_spec(spec, exclude={ref.node_id})
        return True

    # ------------------------------------------------------------------ get
    def get(self, ref: ClusterRef, timeout: Optional[float] = 60.0) -> Any:
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            remaining = (None if deadline is None
                         else deadline - time.monotonic())
            if remaining is not None and remaining <= 0:
                raise GetTimeoutError(
                    f"get of {ref.object_id.hex()[:8]} timed out")
            wait_s = min(remaining or 0.5, 0.5)
            reply = self.gcs.call(
                "object_wait_location", object_id=ref.object_id,
                timeout_s=wait_s, timeout=wait_s + 10.0)
            locations = reply["locations"]
            if not locations:
                # no copy anywhere: producer may have died — resubmit if
                # the producing node is gone and lineage allows
                if ref.node_id and not self._node_alive(ref.node_id):
                    if not self._resubmit(ref):
                        raise WorkerCrashedError(
                            f"object {ref.object_id.hex()[:8]} lost and "
                            "not recoverable")
                continue
            payload = self._fetch(locations, ref.object_id)
            if payload is None:
                continue  # all holders died mid-fetch; loop re-resolves
            is_error, data = payload
            value = protocol.loads_flat(data)
            if is_error:
                # the stored payload is the task's exception: re-raise it
                # in the driver (reference: RayTaskError re-raise on get)
                if isinstance(value, BaseException):
                    raise value
                raise RuntimeError(str(value))
            return value

    def wait(self, refs: List[ClusterRef], num_returns: int = 1,
             timeout: Optional[float] = None
             ) -> Tuple[List[ClusterRef], List[ClusterRef]]:
        """ray.wait semantics over the cluster: ready = a location
        exists in the GCS directory (the object is materialized on some
        node)."""
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        pending = list(refs)
        ready: List[ClusterRef] = []
        while True:
            still: List[ClusterRef] = []
            for ref in pending:
                reply = self.gcs.call("object_locations",
                                      object_id=ref.object_id,
                                      timeout=10.0)
                if reply["locations"]:
                    ready.append(ref)
                else:
                    still.append(ref)
            pending = still
            if len(ready) >= num_returns or not pending:
                break
            if deadline is not None and time.monotonic() >= deadline:
                break
            time.sleep(0.05)
        ready_set = {id(r) for r in ready[:num_returns]}
        ordered_ready = [r for r in refs if id(r) in ready_set]
        return (ordered_ready,
                [r for r in refs if id(r) not in ready_set])

    def _node_alive(self, node_id: str) -> bool:
        view = self.cluster_view()
        info = view["nodes"].get(node_id)
        return bool(info and info["alive"])

    def _node_address(self, node_id: str) -> Optional[str]:
        view = self.cluster_view()
        info = view["nodes"].get(node_id)
        return info["address"] if info and info["alive"] else None

    # --------------------------------------------------------- task state
    def task_state(self, ref: ClusterRef) -> str:
        """State of the task that produces ``ref`` on its assigned node:
        queued | running | done | failed | unknown | lost (node
        dead). The driver-side view of the reference's task-state API
        (GetTaskEvents over the GCS)."""
        address = self._node_address(ref.node_id) if ref.node_id else None
        if address is None:
            return "lost"
        try:
            reply = self._raylet(address).call(
                "task_state", task_id=ref.task_id, timeout=10.0)
        except (RpcConnectionError, TimeoutError):
            return "lost"
        return reply["state"]

    def wait_task(self, ref: ClusterRef,
                  timeout: float = 10.0) -> str:
        """Block on the producing raylet until the task reaches a
        terminal state (or the timeout lapses); returns the final
        state observed (terminal or not)."""
        address = self._node_address(ref.node_id) if ref.node_id else None
        if address is None:
            return "lost"
        try:
            reply = self._raylet(address).call(
                "wait_task", task_id=ref.task_id, timeout_s=timeout,
                timeout=timeout + 10.0)
        except (RpcConnectionError, TimeoutError):
            return "lost"
        return reply["state"]

    def _fetch(self, locations: List[dict], object_id: bytes
               ) -> Optional[Tuple[bool, bytes]]:
        from ray_tpu.cluster.byte_store import attach_shm, shm_key
        from ray_tpu.cluster.rpc import fetch_object

        for loc in locations:
            try:
                client = self._raylet(loc["address"])
            except (RpcConnectionError, OSError):
                continue
            # same-host fast path: read the holder's shm segment
            # directly instead of streaming over TCP (mirrors the
            # raylet-to-raylet path in raylet_server._fetch_from)
            try:
                info = client.call("get_object_info",
                                   object_id=object_id, timeout=10.0)
            except (RpcConnectionError, TimeoutError):
                continue
            if not info.get("present"):
                continue
            if info.get("shm_path"):
                seg = attach_shm(info["shm_path"])
                if seg is not None:
                    try:
                        payload = seg.get_bytes(shm_key(object_id))
                    except Exception:
                        payload = None
                    if payload is not None:
                        # trailer-aware slice + digest check (integrity
                        # plane): the bytes copied out of the holder's
                        # segment are verified before deserialization;
                        # a mismatch falls through to the chunked
                        # stream, which re-verifies end to end
                        from ray_tpu.cluster import integrity
                        from ray_tpu.exceptions import (
                            ObjectCorruptedError,
                        )

                        body, t_crc = integrity.split_shm(
                            payload, info["size"])
                        if body is not None:
                            crc = info.get("crc")
                            crc = crc if crc is not None else t_crc
                            try:
                                if integrity.verify_shm_reads():
                                    integrity.verify(body, crc,
                                                     "shm_read",
                                                     object_id)
                                return info["is_error"], bytes(body)
                            except ObjectCorruptedError:
                                logger.warning(
                                    "shm read of %s failed its digest;"
                                    " falling back to the stream",
                                    object_id.hex()[:8])
            result = fetch_object(client, object_id)
            if result is not None:
                return result
        return None

    def broadcast(self, ref: ClusterRef, node_ids: List[str]) -> int:
        """Pre-place an object's payload on a set of nodes through the
        push plane. With the data-plane pipeline ON (default) the
        driver plans ONE chunk tree (topology knob: binomial | chain |
        flat | auto) and hands the nested plan to the source raylet in
        a single push — interior nodes cut-through forward each chunk
        the moment it verifies, so tree depth costs latency per CHUNK,
        not per object, and same-host receivers adopt the producer's
        segment outright (zero bytes moved). OFF reproduces the exact
        pre-pipeline round-by-round driver fan-out (parity-pinned).
        Unconfirmed nodes converge through a pull_object fallback.
        Returns the number of nodes that confirmed a resident copy."""
        from ray_tpu._private.config import Config

        if not Config.instance().data_plane_pipeline_enabled:
            return self._broadcast_legacy(ref, node_ids)
        return self._broadcast_pipelined(ref, node_ids)

    def _broadcast_pipelined(self, ref: ClusterRef,
                             node_ids: List[str]) -> int:
        from ray_tpu._private.config import Config

        cfg = Config.instance()
        view = self.cluster_view()
        addr_of = {nid: info["address"]
                   for nid, info in view["nodes"].items()
                   if info["alive"]}
        reply = self.gcs.call("object_locations",
                              object_id=ref.object_id, timeout=10.0)
        holders = [loc["node_id"] for loc in reply["locations"]
                   if loc["node_id"] in addr_of]
        targets = [n for n in node_ids
                   if n not in holders and n in addr_of]
        if not targets or not holders:
            self.last_broadcast_plan = {"topology": "none", "depth": 0,
                                        "fanout": 0, "targets": 0}
            return 0
        src = holders[0]
        topology = cfg.data_plane_topology
        if topology == "auto":
            # small fans: the per-target pull dedup is simpler and the
            # tree's pipeline has nothing to overlap; larger fans get
            # the binomial chunk tree
            topology = "flat" if len(targets) <= 2 else "binomial"

        confirmed_set: set = set()
        if topology == "flat":
            plan = None
            calls = []
            for dst in targets:
                try:
                    calls.append((dst, self._raylet(addr_of[dst]).call_async(
                        "pull_object", object_id=ref.object_id,
                        from_address=addr_of[src])))
                except (RpcConnectionError, OSError):
                    continue
            for dst, call in calls:
                try:
                    if call.result(timeout=300.0).get("ok"):
                        confirmed_set.add(dst)
                except Exception:
                    continue  # unconfirmed: the re-pull rounds converge
        else:
            plan = (_chain_plan(targets, addr_of) if topology == "chain"
                    else _binomial_plan(targets, addr_of))
            for addr, subtree in plan:
                try:
                    self._raylet(addr_of[src]).call(
                        "push_object", object_id=ref.object_id,
                        to_address=addr, downstream=subtree or None,
                        timeout=60.0)
                except (RpcConnectionError, TimeoutError) as e:
                    # source unreachable for this child: the re-pull
                    # fallback below still converges the subtree
                    logger.debug(
                        "broadcast: push_object %s -> %s failed (%r); "
                        "subtree converges via re-pull",
                        addr_of[src], addr, e)
        self.last_broadcast_plan = {
            "topology": topology,
            "depth": _plan_depth(plan) if plan else 1,
            "fanout": len(plan) if plan else len(targets),
            "targets": len(targets)}
        # confirm + converge: wait on each target's store, then re-pull
        # stragglers (a dead interior node orphans its subtree; the
        # survivors fetch from any confirmed holder — satellite
        # contract: subtree converges via re-pull)
        deadline = time.monotonic() + 300.0
        for round_no in range(3):
            pending = [d for d in targets if d not in confirmed_set]
            if not pending or time.monotonic() >= deadline:
                break
            for dst in pending:
                if time.monotonic() >= deadline:
                    break
                try:
                    client = self._raylet(addr_of[dst])
                    if round_no > 0:
                        # straggler: actively re-pull instead of waiting
                        if client.call("pull_object",
                                       object_id=ref.object_id,
                                       timeout=70.0).get("ok"):
                            confirmed_set.add(dst)
                            continue
                    present = client.call(
                        "wait_object", object_id=ref.object_id,
                        timeout_s=(5.0 if round_no == 0 else 1.0),
                        timeout=60.0)["present"]
                    if present:
                        confirmed_set.add(dst)
                except RpcConnectionError:
                    continue  # node died mid-broadcast: stays unconfirmed
                except TimeoutError:
                    continue
        return len(confirmed_set)

    def _broadcast_legacy(self, ref: ClusterRef,
                          node_ids: List[str]) -> int:
        """The exact pre-pipeline broadcast (data_plane_pipeline_enabled
        off): round-by-round driver-coordinated binomial fan-out — each
        round, every node that already holds a copy pushes to one new
        node, so a B-byte broadcast to N nodes costs any single holder
        only O(log N) * B upload instead of N * B (reference broadcast
        pattern stressed by the 1 GiB -> 50 node object_store baseline;
        push path: object_manager.cc:302 + push_manager.h). Returns the
        number of nodes that confirmed a resident copy."""
        view = self.cluster_view()
        addr_of = {nid: info["address"]
                   for nid, info in view["nodes"].items()
                   if info["alive"]}
        reply = self.gcs.call("object_locations",
                              object_id=ref.object_id, timeout=10.0)
        # a dead node's location entry may linger until the async
        # deregistration lands: only fan out from holders that are alive
        holders = [loc["node_id"] for loc in reply["locations"]
                   if loc["node_id"] in addr_of]
        targets = [n for n in node_ids
                   if n not in holders and n in addr_of]
        self.last_broadcast_plan = {"topology": "legacy", "depth": 0,
                                    "fanout": 0, "targets": len(targets)}
        if not targets:
            return 0
        confirmed = 0
        pending = list(targets)
        rounds_without_progress = 0
        while pending and rounds_without_progress < 3:
            # every current holder feeds one pending target this round
            requested = []
            for src, dst in zip(list(holders), list(pending)):
                try:
                    # generous: enqueueing a push is cheap, but a node
                    # mid-transfer of GiB-scale chunks answers slowly
                    # on a saturated host
                    ok = self._raylet(addr_of[src]).call(
                        "push_object", object_id=ref.object_id,
                        to_address=addr_of[dst],
                        timeout=60.0).get("ok")
                except (RpcConnectionError, TimeoutError):
                    ok = False
                if ok:
                    requested.append(dst)
            pending = [d for d in pending if d not in set(requested)]
            # wait for this round's copies before fanning out from them
            progressed = False
            for dst in requested:
                client = self._raylet(addr_of[dst])
                deadline = time.monotonic() + 300.0
                while time.monotonic() < deadline:
                    try:
                        # block in the receiver's store instead of
                        # hot-polling has_object: wait_object parks on
                        # the store's condition variable and returns
                        # the moment the copy materializes
                        present = client.call(
                            "wait_object", object_id=ref.object_id,
                            timeout_s=5.0, timeout=60.0)["present"]
                    except RpcConnectionError:
                        # node DIED mid-broadcast: stays unconfirmed —
                        # partial results are the contract
                        break
                    except TimeoutError:
                        # merely slow (GiB transfer on a saturated
                        # host): keep waiting until the 300s deadline
                        continue
                    if present:
                        holders.append(dst)
                        confirmed += 1
                        progressed = True
                        break
            rounds_without_progress = (
                0 if progressed else rounds_without_progress + 1)
        return confirmed

    # ------------------------------------------------------------------ put
    def put(self, value: Any) -> ClusterRef:
        object_id = os.urandom(28)
        payload = protocol.dumps_flat(value)
        exclude: set = set()
        last_err: Optional[BaseException] = None
        # spill to the next holder on conn failure, like submits do: a
        # put routed to a just-died node (no heartbeat verdict yet) is
        # retriable on any other holder — and marking the node suspect
        # keeps the NEXT put from re-picking the corpse
        for _ in range(3):
            target = self._pick_node({}, exclude)
            if target is None:
                break
            nid, info = target
            try:
                self._raylet(info["address"]).call(
                    "put_object", object_id=object_id, payload=payload,
                    timeout=60.0)
            except (RpcConnectionError, TimeoutError) as e:
                self._mark_suspect(nid)
                exclude.add(nid)
                last_err = e
                continue
            return ClusterRef(object_id, "", nid)
        if last_err is not None:
            raise last_err
        raise RuntimeError("no alive nodes to hold the object")

    # ---------------------------------------------------------------- actors
    def create_actor(self, cls, args: tuple = (),
                     kwargs: Optional[dict] = None,
                     resources: Optional[Dict[str, float]] = None,
                     max_restarts: int = 0, name: str = ""
                     ) -> ClusterActorHandle:
        actor_id = self._next_id("actor")
        packed_args = ([self._pack_arg(a) for a in args],
                       {k: self._pack_arg(v)
                        for k, v in (kwargs or {}).items()})
        if self._batching:
            # coalesced path: the row rides an actor_create_batch frame
            # with everything else submitted this linger window; the
            # per-row reply carries the same view the serial RPC would
            # row token: a frame retried after a dropped reply (or
            # duplicated by the fault plane) replays this row from the
            # GCS dedupe cache instead of double-registering the actor
            view = self._create_batcher.submit({
                "actor_id": actor_id,
                "cls_bytes": protocol.dumps(cls),
                "args_bytes": protocol.dumps(packed_args),
                "resources": dict(resources or {"CPU": 1.0}),
                "max_restarts": max_restarts, "name": name,
                "token": self._next_id("rowtok"),
            }, timeout=120.0)
            if view.get("state") == "ERROR":
                # API parity with the serial path, where the GCS raises
                # this typed across the wire (e.g. name already taken)
                raise ValueError(
                    view.get("error", "actor creation failed"))
        else:
            # request token: the resilient GCS client may retry this
            # call after a lost ack, and the fault plane may duplicate
            # the frame — either way the mutation applies exactly once
            view = self.gcs.call(
                "actor_create", actor_id=actor_id,
                cls_bytes=protocol.dumps(cls),
                args_bytes=protocol.dumps(packed_args),
                resources=dict(resources or {"CPU": 1.0}),
                max_restarts=max_restarts, name=name,
                token=self._next_id("tok"), timeout=120.0)
        if view["state"] == "PENDING":
            logger.info("actor %s pending (no capacity yet)", actor_id)
        return ClusterActorHandle(self, actor_id)

    def get_actor(self, name: str) -> ClusterActorHandle:
        view = self.gcs.call("actor_by_name", name=name, timeout=10.0)
        return ClusterActorHandle(self, view["actor_id"])

    def actor_state(self, handle_or_id) -> dict:
        """The GCS's current record for an actor (state, node,
        incarnation, restarts, init_error) — a non-blocking snapshot;
        ``_actor_call`` uses the blocking ``actor_wait`` instead."""
        actor_id = getattr(handle_or_id, "actor_id", handle_or_id)
        return self.gcs.call("actor_get", actor_id=actor_id,
                             timeout=10.0)

    def _actor_call(self, actor_id: str, method: str, args: tuple,
                    kwargs: dict, timeout: float = 60.0) -> Any:
        """Route to the actor's current node; on failure re-resolve from
        the GCS (restart may have moved it) and retry until the actor is
        DEAD or the timeout lapses."""
        packed = ([self._pack_arg(a) for a in args],
                  {k: self._pack_arg(v) for k, v in kwargs.items()})
        args_bytes = protocol.dumps(packed)
        deadline = time.monotonic() + timeout
        last_err: Optional[BaseException] = None
        backoff = 0.05
        while time.monotonic() < deadline:
            # actor_wait long-polls server-side until the actor settles
            # (ALIVE-with-address or DEAD) — replaces the old
            # actor_get + flat sleep(0.1) hot-poll that burned a GCS
            # round-trip every 100ms per waiting caller
            wait_s = min(5.0, max(0.1, deadline - time.monotonic()))
            view = self.gcs.call("actor_wait", actor_id=actor_id,
                                 timeout_s=wait_s, timeout=wait_s + 10.0)
            state = view["state"]
            if state == "DEAD":
                detail = view.get("init_error") or ""
                raise ActorDiedError(
                    f"actor {actor_id} is dead "
                    f"(restarts used: {view['restarts_used']})"
                    + (f": {detail}" if detail else ""))
            if state != "ALIVE" or "address" not in view:
                # long-poll lapsed with the actor still in limbo:
                # capped exponential backoff before re-polling
                time.sleep(backoff)
                backoff = min(backoff * 2, 1.0)
                continue
            backoff = 0.05
            try:
                result = self._raylet(view["address"]).call(
                    "actor_call", actor_id=actor_id, method_name=method,
                    args_bytes=args_bytes,
                    timeout=max(1.0, deadline - time.monotonic()))
                return protocol.loads(result)
            except WorkerCrashedError as e:
                # the actor process died EXECUTING this call: surface it —
                # actor tasks are not retried by default (reference:
                # max_task_retries=0); the GCS restarts the actor in the
                # background for future calls
                raise RayActorError(
                    f"actor {actor_id} died while executing "
                    f"{method}: {e}") from e
            except (RpcConnectionError, TimeoutError, KeyError,
                    ConnectionError, OSError) as e:
                last_err = e
                time.sleep(0.2)  # node died or actor moving; re-resolve
        raise GetTimeoutError(
            f"actor call {actor_id}.{method} did not complete: "
            f"{last_err!r}")

    def kill_actor(self, handle: ClusterActorHandle,
                   no_restart: bool = True) -> None:
        if self._batching:
            # coalesced path: rides an actor_kill_batch frame; the GCS
            # marks every row DEAD under one lock hold and sends each
            # hosting raylet one kill frame instead of a serial
            # 10s-timeout RPC per actor
            self._kill_batcher.submit(
                {"actor_id": handle.actor_id, "no_restart": no_restart,
                 "token": self._next_id("rowtok")},
                timeout=60.0)
            return
        self.gcs.call("actor_kill", actor_id=handle.actor_id,
                      no_restart=no_restart,
                      token=self._next_id("tok"), timeout=30.0)

    # ------------------------------------------------------------------- PG
    def create_placement_group(self, bundles: List[Dict[str, float]],
                               strategy: str = "PACK") -> str:
        pg_id = os.urandom(18).hex()
        view = self.gcs.call("pg_create", pg_id=pg_id, bundles=bundles,
                             strategy=strategy,
                             token=self._next_id("tok"), timeout=120.0)
        return view["pg_id"]

    def pg_info(self, pg_id: str) -> dict:
        return self.gcs.call("pg_get", pg_id=pg_id, timeout=10.0)

    def remove_placement_group(self, pg_id: str) -> None:
        self.gcs.call("pg_remove", pg_id=pg_id,
                      token=self._next_id("tok"), timeout=60.0)

    # ----------------------------------------------------------------- free
    def free(self, refs: List[ClusterRef]) -> int:
        """Eagerly drop the payloads behind ``refs`` from every node
        holding a copy (``ray.internal.free``): one ``free_objects``
        RPC per holder node batching that node's ids. Lineage is NOT
        consulted — a freed object is gone even if its producer could
        rerun. Returns the number of node-level free RPCs that landed.
        """
        by_address: Dict[str, List[bytes]] = {}
        for ref in refs:
            reply = self.gcs.call("object_locations",
                                  object_id=ref.object_id, timeout=10.0)
            for loc in reply["locations"]:
                by_address.setdefault(loc["address"], []).append(
                    ref.object_id)
            with self._lock:
                self._lineage.pop(ref.object_id, None)
                self._retries.pop(ref.object_id, None)
        landed = 0
        for address, object_ids in by_address.items():
            try:
                self._raylet(address).call(
                    "free_objects", object_ids=object_ids, timeout=30.0)
                landed += 1
            except (RpcConnectionError, TimeoutError) as e:
                # holder died mid-free: its store dies with it and the
                # GCS drops the locations on node death
                logger.debug("free_objects on %s failed: %r", address, e)
        return landed

    # ------------------------------------------------------------------- kv
    def kv_put(self, key: bytes, value: bytes, ns: str = "default") -> None:
        self.gcs.call("kv_put", ns=ns, key=key, value=value, timeout=10.0)

    def kv_get(self, key: bytes, ns: str = "default") -> Optional[bytes]:
        return self.gcs.call("kv_get", ns=ns, key=key, timeout=10.0)

    def kv_del(self, key: bytes, ns: str = "default") -> bool:
        reply = self.gcs.call("kv_del", ns=ns, key=key, timeout=10.0)
        return bool(reply["deleted"])

    def kv_keys(self, prefix: bytes = b"", ns: str = "default"
                ) -> List[bytes]:
        return self.gcs.call("kv_keys", ns=ns, prefix=prefix,
                             timeout=10.0)

    # ------------------------------------------------------------- overview
    def job_view(self) -> dict:
        """Cluster-wide object/actor/PG counts (the `ray status`
        summary surface)."""
        return self.gcs.call("job_view", timeout=10.0)

    def close(self) -> None:
        self.gcs.close()
        for c in self._raylet_clients.values():
            c.close()
