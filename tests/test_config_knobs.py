"""Every Config knob exercised at a non-default value.

raycheck RC14 (knob hygiene) requires each ``Config`` knob to be read
somewhere, documented in the README knob tables, and covered by at
least one test that sets a NON-default value. This file is that
coverage floor: the ``NON_DEFAULTS`` table names every knob with a
deliberately non-default value, a completeness check pins the table
against ``dataclasses.fields(Config)`` (a new knob without a row here
fails), and the override plumbing — env vars and
``apply_system_config`` — is driven with the whole table. Behavioral
spot-checks then observe the governed behavior for the knobs whose
wiring landed with RC14 itself (lineage byte budget, autoscaler
defaults, timeline gating).
"""

import threading
from collections import OrderedDict
from dataclasses import fields

import pytest

from ray_tpu._private.config import Config

# One deliberately non-default value per knob. Values are arbitrary
# but type-correct; the completeness test asserts each differs from
# the shipped default, so a default drifting onto its row is caught.
NON_DEFAULTS = {
    "scheduler_spread_threshold": 2.25,
    "scheduler_cap_per_class": False,
    "scheduler_tick_period_ms": 17,
    "scheduler_max_tasks_per_tick": 16391,
    "scheduler_batch_threshold": 23,
    "scheduler_use_vectorized_policy": False,
    "scheduler_device_solve_min_cells": 8199,
    "scheduler_pipeline_enabled": False,
    "scheduler_matrix_sync_period": 71,
    "scheduler_pipeline_debug_check": True,
    "maximum_startup_concurrency": 15,
    "idle_worker_lease_timeout_ms": 1007,
    "raylet_heartbeat_period_ms": 107,
    "num_heartbeats_timeout": 37,
    "rpc_connect_timeout_s": 21.25,
    "task_retry_delay_ms": 7,
    "rpc_retry_window_s": 61.25,
    "rpc_retry_base_ms": 57,
    "rpc_retry_max_backoff_ms": 2007,
    "overload_enabled": False,
    "rpc_server_max_dispatch_threads": 135,
    "rpc_server_queue_depth": 1031,
    "rpc_retry_budget_fraction": 1.65,
    "rpc_retry_budget_initial": 21.25,
    "rpc_retry_budget_cap": 101.25,
    "rpc_breaker_failure_threshold": 15,
    "rpc_breaker_reset_s": 3.25,
    "raylet_max_queued_tasks": 100007,
    "submit_backpressure_timeout_s": 121.25,
    "push_manager_max_queued": 519,
    "serve_resilience_enabled": False,
    "serve_health_check_period_s": 1.75,
    "serve_health_check_timeout_s": 5.25,
    "serve_health_check_failure_threshold": 10,
    "serve_router_backpressure_timeout_s": 5.25,
    "serve_drain_grace_s": 1.75,
    "integrity_enabled": False,
    "integrity_verify_on_get": True,
    "integrity_verify_shm_reads": False,
    "pg_prepare_lease_s": 61.25,
    "fault_plan": "preempt_node:p=0.0",
    "byte_store_sweep_min_age_s": 601.25,
    "max_direct_call_object_size": 102407,
    "object_chunk_size": 5242887,
    "object_store_memory": 2147483655,
    "pull_manager_admission_fraction": 2.85,
    "object_timeout_ms": 107,
    "same_host_zero_copy_reads": False,
    "object_spilling_threshold": 2.85,
    "spill_directory": "/tmp/raytpu_knob_spill",
    "object_store_full_max_retries": 12,
    "actor_creation_min_retries": 7,
    "max_pending_calls_default": 6,
    "actor_restart_backoff_ms": 7,
    "worker_pool_enabled": False,
    "worker_pool_warm_size": 11,
    "worker_pool_preimport": "json",
    "actor_batch_max": 519,
    "actor_batch_linger_s": 1.254,
    "actor_batch_fanout": 23,
    "dispatch_fastlane_enabled": False,
    "dispatch_batch_max": 519,
    "dispatch_batch_linger_s": 1.251,
    "dispatch_inline_arg_max": 65543,
    "data_plane_pipeline_enabled": False,
    "data_plane_chunk_bytes": 1048583,
    "data_plane_window": 15,
    "data_plane_topology": "chain",
    "data_plane_stream_only": True,
    "data_plane_inbound_stale_s": 61.25,
    "fastlane_breaker_enabled": False,
    "fastlane_breaker_threshold": 12,
    "fastlane_breaker_reset_s": 5.25,
    "chunk_tree_failover_enabled": False,
    "tick_epoch_fencing": False,
    "drain_plane_enabled": False,
    "drain_deadline_s": 21.25,
    "preempt_notice_s": 5.25,
    "batch_fanout_join_timeout_s": 31.25,
    "actor_executor_wake_s": 0.25,
    "autoscaler_idle_timeout_s": 61.25,
    "autoscaler_demand_threshold": 8,
    "autoscaler_update_interval_s": 3.25,
    "max_lineage_bytes": 1073741831,
    "max_lineage_entries": 10007,
    "enable_object_reconstruction": False,
    "gcs_pull_resource_period_ms": 107,
    "gcs_storage_backend": "file",
    "event_stats": False,
    "metrics_report_interval_ms": 1007,
    "observability_plane_enabled": False,
    "tracing_sample_rate": 3.25,
    "flight_recorder_capacity": 4103,
    "collective_op_timeout_s": 1201.25,
    "memory_monitor_interval_ms": 7,
}


def _public_fields():
    return [f.name for f in fields(Config)
            if not f.name.startswith("_")]


def test_non_defaults_table_is_complete_and_non_default():
    """Every knob has a row, every row differs from the default.

    This is the RC14 contract made executable: adding a knob to
    Config without extending this table (and hence without any
    non-default coverage) is a test failure, not a silent gap."""
    names = _public_fields()
    missing = sorted(set(names) - set(NON_DEFAULTS))
    stale = sorted(set(NON_DEFAULTS) - set(names))
    assert not missing, f"knobs without a non-default row: {missing}"
    assert not stale, f"rows for removed knobs: {stale}"


def test_non_defaults_differ_from_defaults():
    defaults = Config()
    for name, value in NON_DEFAULTS.items():
        assert getattr(defaults, name) != value, \
            f"{name}: table value {value!r} equals the shipped default"


def test_env_override_roundtrip(monkeypatch):
    """RAY_TPU_<name> env overrides land for every knob, with type
    coercion (bool strings, int strings, float strings)."""
    for name, value in NON_DEFAULTS.items():
        if isinstance(value, bool):
            env = "true" if value else "false"
        else:
            env = str(value)
        monkeypatch.setenv(f"RAY_TPU_{name}", env)
    cfg = Config._from_env()
    for name, value in NON_DEFAULTS.items():
        assert getattr(cfg, name) == value, name


def test_apply_system_config_roundtrip():
    cfg = Config()
    cfg.apply_system_config(dict(NON_DEFAULTS))
    for name, value in NON_DEFAULTS.items():
        assert getattr(cfg, name) == value, name


def test_apply_system_config_rejects_unknown_knob():
    cfg = Config()
    with pytest.raises(ValueError):
        cfg.apply_system_config({"not_a_real_knob": 1})


# --------------------------------------------------------------------------
# behavior spot-checks for the knobs wired alongside RC14
# --------------------------------------------------------------------------


@pytest.fixture
def _config_singleton():
    """Hand the test the live singleton and restore it afterwards."""
    Config.reset()
    try:
        yield Config.instance()
    finally:
        Config.reset()


def test_max_lineage_bytes_evicts_by_size(_config_singleton):
    """A tiny byte budget evicts oldest lineage entries even when the
    entry-count cap is far away."""
    from ray_tpu.core.runtime import Runtime
    from ray_tpu.core.task_spec import (TaskID, TaskKind, TaskSpec,
                                        JobID)

    _config_singleton._set("max_lineage_bytes", 3_000)
    _config_singleton._set("max_lineage_entries", 10_000)

    class _Stub:
        record_lineage = Runtime.record_lineage

    stub = _Stub()
    stub._lineage = OrderedDict()
    stub._lineage_cost = {}
    stub._lineage_bytes = 0
    stub._lineage_lock = threading.Lock()

    def spec(i, payload):
        return TaskSpec(
            kind=TaskKind.NORMAL,
            task_id=TaskID(i.to_bytes(24, "big")),
            job_id=JobID(b"\x00" * 4),
            parent_task_id=TaskID(b"\x01" * 24),
            name=f"t{i}", func=lambda: None,
            args=(payload,))

    # each entry costs 256 overhead + 1000 payload; budget 3000 holds
    # at most two
    for i in range(5):
        stub.record_lineage(spec(i, b"x" * 1000))
    assert len(stub._lineage) == 2
    assert stub._lineage_bytes <= 3_000
    # the survivors are the two most recent
    kept = sorted(int.from_bytes(t.binary(), "big")
                  for t in stub._lineage)
    assert kept == [3, 4]


def test_autoscaler_knob_defaults_and_yaml_precedence(_config_singleton):
    from ray_tpu.autoscaler.autoscaler import StandardAutoscaler
    from ray_tpu.autoscaler.node_provider import NodeProvider

    _config_singleton._set("autoscaler_idle_timeout_s", 123.0)
    _config_singleton._set("autoscaler_demand_threshold", 9)
    provider = NodeProvider({}, "t")

    # YAML names neither idle key: the Config knobs are the defaults
    a = StandardAutoscaler({"available_node_types": {}}, provider)
    assert a.idle_timeout_s == 123.0
    assert a.demand_threshold == 9

    # YAML keys win over the knobs
    b = StandardAutoscaler(
        {"available_node_types": {},
         "idle_timeout_minutes": 2, "demand_threshold": 1}, provider)
    assert b.idle_timeout_s == 120.0
    assert b.demand_threshold == 1


def test_autoscaler_demand_threshold_gates_scale_up(_config_singleton):
    """Pending demand below the threshold plans no demand-driven
    launches (the min_workers floor is still honored — here zero)."""
    from ray_tpu.autoscaler.autoscaler import StandardAutoscaler
    from ray_tpu.autoscaler.node_provider import NodeProvider

    class _Provider(NodeProvider):
        def __init__(self):
            super().__init__({}, "t")
            self.created = []

        def non_terminated_nodes(self, tag_filters):
            return []

        def node_tags(self, node_id):
            return {}

        def create_node(self, node_config, tags, count):
            self.created.append((tags, count))

    def mk(threshold):
        p = _Provider()
        a = StandardAutoscaler(
            {"available_node_types":
                {"cpu": {"resources": {"CPU": 4}, "min_workers": 0,
                         "max_workers": 4}},
             "max_workers": 4, "demand_threshold": threshold}, p)
        a.load_metrics.pending_demands = [{"CPU": 1.0}]
        return a, p

    below, p_below = mk(threshold=2)   # 1 pending < 2
    assert below.update(runtime=None) == {}
    assert p_below.created == []

    at, p_at = mk(threshold=1)         # 1 pending >= 1
    plan = at.update(runtime=None)
    assert sum(plan.values()) >= 1
    assert p_at.created
