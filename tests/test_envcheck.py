"""Env-var validation: bad tuning values fail fast with errors that
name the variable and the violated constraint, instead of dying in a
bare int() traceback or assert deep inside the consumer module."""

import pytest

from tigerbeetle_tpu import envcheck
from tigerbeetle_tpu.state_machine import waves


def test_env_int_rejects_garbage(monkeypatch):
    monkeypatch.setenv("TB_DEV_WINDOW", "ninety-six")
    with pytest.raises(envcheck.EnvVarError, match="TB_DEV_WINDOW"):
        envcheck.env_int("TB_DEV_WINDOW", 96, minimum=1)


def test_env_int_bounds(monkeypatch):
    monkeypatch.setenv("TB_DEV_PROBE_EVERY", "0")
    with pytest.raises(envcheck.EnvVarError, match="must be >= 1"):
        envcheck.env_int("TB_DEV_PROBE_EVERY", 8, minimum=1)
    monkeypatch.setenv("TB_DEV_PROBE_EVERY", "512")
    assert envcheck.env_int("TB_DEV_PROBE_EVERY", 8, minimum=1) == 512


def test_env_int_default_when_unset(monkeypatch):
    monkeypatch.delenv("TB_DEV_WINDOW", raising=False)
    assert envcheck.env_int("TB_DEV_WINDOW", 96, minimum=1) == 96


def test_tb_group_commit_max_us_validated(monkeypatch):
    monkeypatch.setenv("TB_GROUP_COMMIT_MAX_US", "soon")
    with pytest.raises(envcheck.EnvVarError, match="TB_GROUP_COMMIT_MAX_US"):
        envcheck.group_commit_max_us()
    monkeypatch.setenv("TB_GROUP_COMMIT_MAX_US", "-1")
    with pytest.raises(envcheck.EnvVarError, match="must be >= 0"):
        envcheck.group_commit_max_us()
    monkeypatch.setenv("TB_GROUP_COMMIT_MAX_US", "0")  # 0 = disabled
    assert envcheck.group_commit_max_us() == 0
    monkeypatch.setenv("TB_GROUP_COMMIT_MAX_US", "5000")
    assert envcheck.group_commit_max_us() == 5000
    monkeypatch.delenv("TB_GROUP_COMMIT_MAX_US")
    assert envcheck.group_commit_max_us() == 2000  # default on


def test_tb_ckpt_async_validated(monkeypatch):
    monkeypatch.setenv("TB_CKPT_ASYNC", "yes")
    with pytest.raises(envcheck.EnvVarError, match="TB_CKPT_ASYNC"):
        envcheck.ckpt_async()
    monkeypatch.setenv("TB_CKPT_ASYNC", "2")
    with pytest.raises(envcheck.EnvVarError, match="must be <= 1"):
        envcheck.ckpt_async()
    monkeypatch.setenv("TB_CKPT_ASYNC", "0")
    assert envcheck.ckpt_async() == 0
    monkeypatch.delenv("TB_CKPT_ASYNC")
    assert envcheck.ckpt_async() == 1  # default on


def test_tb_ckpt_async_disables_worker(monkeypatch, tmp_path):
    """TB_CKPT_ASYNC=0 keeps the whole checkpoint on the commit path
    (no checkpoint worker), even on FileStorage."""
    from tigerbeetle_tpu import constants as cfg
    from tigerbeetle_tpu.state_machine import CpuStateMachine
    from tigerbeetle_tpu.vsr import replica as vsr_replica
    from tigerbeetle_tpu.vsr.storage import FileStorage, ZoneLayout

    layout = ZoneLayout(config=cfg.TEST_MIN)
    path = str(tmp_path / "data.tb")
    storage = FileStorage(path, layout, create=True)
    vsr_replica.format(storage, 5)
    monkeypatch.setenv("TB_CKPT_ASYNC", "0")
    r = vsr_replica.Replica(storage, 5, CpuStateMachine(cfg.TEST_MIN))
    assert r._ckpt_worker is None
    monkeypatch.setenv("TB_CKPT_ASYNC", "1")
    r2 = vsr_replica.Replica(storage, 5, CpuStateMachine(cfg.TEST_MIN))
    assert r2._ckpt_worker is not None
    r.close()
    r2.close()
    storage.close()


def test_tb_fastpath_decode_validated(monkeypatch):
    monkeypatch.setenv("TB_FASTPATH_DECODE", "fast")
    with pytest.raises(envcheck.EnvVarError, match="TB_FASTPATH_DECODE"):
        envcheck.fastpath_decode()
    monkeypatch.setenv("TB_FASTPATH_DECODE", "2")
    with pytest.raises(envcheck.EnvVarError, match="must be <= 1"):
        envcheck.fastpath_decode()
    monkeypatch.setenv("TB_FASTPATH_DECODE", "0")  # forced legacy path
    assert envcheck.fastpath_decode() == 0
    monkeypatch.delenv("TB_FASTPATH_DECODE")
    assert envcheck.fastpath_decode() == 1  # default: columnar on


def test_tb_fastpath_decode_zero_forces_legacy(monkeypatch, tmp_path):
    """TB_FASTPATH_DECODE=0 must actually pin the server to the
    per-message path (differential runs depend on it), and =1 must
    engage the columnar drain when the native bus supports it."""
    from tigerbeetle_tpu import constants as cfg
    from tigerbeetle_tpu.runtime.native import native_available
    from tigerbeetle_tpu.state_machine import CpuStateMachine

    if not native_available():
        pytest.skip("native runtime not built")
    from tigerbeetle_tpu.runtime.server import (
        ReplicaServer, format_data_file,
    )

    def build(flag):
        monkeypatch.setenv("TB_FASTPATH_DECODE", flag)
        path = str(tmp_path / f"fp{flag}.tb")
        format_data_file(path, cluster=5, config=cfg.TEST_MIN)
        return ReplicaServer(
            path, cluster=5, addresses=["127.0.0.1:0"], replica_index=0,
            state_machine_factory=lambda: CpuStateMachine(cfg.TEST_MIN),
            config=cfg.TEST_MIN,
        )

    off = build("0")
    try:
        assert off._fastpath_decode is False
    finally:
        off.close()
    on = build("1")
    try:
        assert on._fastpath_decode == on.bus.native.supports_drain
    finally:
        on.close()


def test_tb_drain_batch_constraint_named(monkeypatch):
    monkeypatch.setenv("TB_DRAIN_BATCH", "many")
    with pytest.raises(envcheck.EnvVarError, match="TB_DRAIN_BATCH"):
        envcheck.drain_batch_max()
    monkeypatch.setenv("TB_DRAIN_BATCH", "4")
    with pytest.raises(envcheck.EnvVarError, match="per-message rounds"):
        envcheck.drain_batch_max()
    monkeypatch.setenv("TB_DRAIN_BATCH", str(1 << 17))
    with pytest.raises(envcheck.EnvVarError, match="must be <="):
        envcheck.drain_batch_max()
    monkeypatch.setenv("TB_DRAIN_BATCH", "64")
    assert envcheck.drain_batch_max() == 64
    monkeypatch.delenv("TB_DRAIN_BATCH")
    assert envcheck.drain_batch_max() == 4096


def test_tb_waves_mode_validated(monkeypatch):
    monkeypatch.setenv("TB_WAVES", "fast")
    with pytest.raises(envcheck.EnvVarError, match="TB_WAVES"):
        waves.mode()
    for legal in ("auto", "0", "1", "exact", "scan"):
        monkeypatch.setenv("TB_WAVES", legal)
        assert waves.mode() == legal


def test_tb_waves_min_ratio_validated(monkeypatch):
    monkeypatch.setenv("TB_WAVES_MIN_RATIO", "two")
    with pytest.raises(envcheck.EnvVarError, match="TB_WAVES_MIN_RATIO"):
        waves.min_ratio()
    monkeypatch.setenv("TB_WAVES_MIN_RATIO", "1.5")
    assert waves.min_ratio() == 1.5


def test_tb_dev_waves_mode_validated(monkeypatch):
    monkeypatch.setenv("TB_DEV_WAVES", "fast")
    with pytest.raises(envcheck.EnvVarError, match="TB_DEV_WAVES"):
        waves.dev_mode()
    for legal in ("auto", "0", "1"):
        monkeypatch.setenv("TB_DEV_WAVES", legal)
        assert waves.dev_mode() == legal
    monkeypatch.delenv("TB_DEV_WAVES")
    assert waves.dev_mode() == "auto"


def test_tb_waves_chain_max_validated(monkeypatch):
    monkeypatch.setenv("TB_WAVES_CHAIN_MAX", "many")
    with pytest.raises(envcheck.EnvVarError, match="TB_WAVES_CHAIN_MAX"):
        waves.chain_max()
    monkeypatch.setenv("TB_WAVES_CHAIN_MAX", "-1")
    with pytest.raises(envcheck.EnvVarError, match="must be >= 0"):
        waves.chain_max()
    monkeypatch.setenv("TB_WAVES_CHAIN_MAX", "5000")
    with pytest.raises(envcheck.EnvVarError, match="must be <= 4096"):
        waves.chain_max()
    monkeypatch.setenv("TB_WAVES_CHAIN_MAX", "0")  # 0 = chain waves off
    assert waves.chain_max() == 0
    monkeypatch.delenv("TB_WAVES_CHAIN_MAX")
    assert waves.chain_max() == 64


def test_tb_waves_speculate_validated(monkeypatch):
    monkeypatch.setenv("TB_WAVES_SPECULATE", "maybe")
    with pytest.raises(envcheck.EnvVarError, match="TB_WAVES_SPECULATE"):
        waves.spec_mode()
    for legal in ("auto", "0", "1", "force"):
        monkeypatch.setenv("TB_WAVES_SPECULATE", legal)
        assert waves.spec_mode() == legal
    monkeypatch.delenv("TB_WAVES_SPECULATE")
    assert waves.spec_mode() == "auto"


def test_tb_waves_spec_residue_cap_validated(monkeypatch):
    monkeypatch.setenv("TB_WAVES_SPEC_RESIDUE_CAP", "some")
    with pytest.raises(
        envcheck.EnvVarError, match="TB_WAVES_SPEC_RESIDUE_CAP"
    ):
        waves.spec_residue_cap()
    monkeypatch.setenv("TB_WAVES_SPEC_RESIDUE_CAP", "-0.1")
    with pytest.raises(envcheck.EnvVarError, match="must be >= 0"):
        waves.spec_residue_cap()
    # Named constraint: the cap is a FRACTION of the batch.
    monkeypatch.setenv("TB_WAVES_SPEC_RESIDUE_CAP", "1.5")
    with pytest.raises(envcheck.EnvVarError, match="fraction of the batch"):
        waves.spec_residue_cap()
    monkeypatch.setenv("TB_WAVES_SPEC_RESIDUE_CAP", "0.5")
    assert waves.spec_residue_cap() == 0.5
    monkeypatch.delenv("TB_WAVES_SPEC_RESIDUE_CAP")
    assert waves.spec_residue_cap() == 0.25


def test_env_float_minimum(monkeypatch):
    monkeypatch.setenv("TB_DEV_BACKOFF_MS", "-1")
    with pytest.raises(envcheck.EnvVarError, match="TB_DEV_BACKOFF_MS"):
        envcheck.env_float("TB_DEV_BACKOFF_MS", 5.0, minimum=0.0)


def test_env_choice(monkeypatch):
    monkeypatch.delenv("TB_WAVES", raising=False)
    assert envcheck.env_choice("TB_WAVES", "auto", ("auto", "0")) == "auto"
    monkeypatch.setenv("TB_WAVES", "nope")
    with pytest.raises(envcheck.EnvVarError, match="expected one of"):
        envcheck.env_choice("TB_WAVES", "auto", ("auto", "0"))


def test_scrub_jitter_constraint_named():
    from tigerbeetle_tpu.state_machine.device_engine import (
        _scrub_jitter_cap,
        _validate_scrub_jitter,
    )

    with pytest.raises(envcheck.EnvVarError) as err:
        _validate_scrub_jitter(256, 256)
    message = str(err.value)
    assert "TB_DEV_SCRUB_JITTER" in message
    assert "TB_DEV_SCRUB_EVERY" in message
    _validate_scrub_jitter(256, 255)  # boundary is legal
    _validate_scrub_jitter(0, 1_000_000)  # scrub disabled: jitter moot
    assert _scrub_jitter_cap(256, -1) == 32  # auto: an eighth
    assert _scrub_jitter_cap(256, 5) == 5
    assert _scrub_jitter_cap(0, -1) == 0


def test_scrub_jitter_env_parses(monkeypatch):
    monkeypatch.setenv("TB_DEV_SCRUB_JITTER", "sometimes")
    with pytest.raises(envcheck.EnvVarError, match="TB_DEV_SCRUB_JITTER"):
        envcheck.env_int("TB_DEV_SCRUB_JITTER", -1, minimum=-1)
    monkeypatch.setenv("TB_DEV_SCRUB_JITTER", "-2")
    with pytest.raises(envcheck.EnvVarError, match="must be >= -1"):
        envcheck.env_int("TB_DEV_SCRUB_JITTER", -1, minimum=-1)
    monkeypatch.setenv("TB_DEV_SCRUB_JITTER", "17")
    assert envcheck.env_int("TB_DEV_SCRUB_JITTER", -1, minimum=-1) == 17


def test_tb_metrics_validated(monkeypatch):
    monkeypatch.setenv("TB_METRICS", "maybe")
    with pytest.raises(envcheck.EnvVarError, match="TB_METRICS"):
        envcheck.metrics_enabled()
    monkeypatch.setenv("TB_METRICS", "2")
    with pytest.raises(envcheck.EnvVarError, match="must be <= 1"):
        envcheck.metrics_enabled()
    monkeypatch.setenv("TB_METRICS", "0")
    assert envcheck.metrics_enabled() == 0
    monkeypatch.delenv("TB_METRICS")
    assert envcheck.metrics_enabled() == 1  # default on


def test_tb_trace_validated(monkeypatch):
    monkeypatch.setenv("TB_TRACE", "perfetto")
    with pytest.raises(
        envcheck.EnvVarError, match="TB_TRACE.*none/json"
    ):
        envcheck.trace_backend()
    monkeypatch.setenv("TB_TRACE", "json")
    assert envcheck.trace_backend() == "json"
    monkeypatch.delenv("TB_TRACE")
    assert envcheck.trace_backend() == "none"  # default off


def test_tb_trace_exemplars_validated(monkeypatch):
    monkeypatch.setenv("TB_TRACE_EXEMPLARS", "lots")
    with pytest.raises(envcheck.EnvVarError, match="TB_TRACE_EXEMPLARS"):
        envcheck.trace_exemplars()
    monkeypatch.setenv("TB_TRACE_EXEMPLARS", "0")
    with pytest.raises(envcheck.EnvVarError, match="must be >= 1"):
        envcheck.trace_exemplars()
    monkeypatch.setenv("TB_TRACE_EXEMPLARS", "64")
    assert envcheck.trace_exemplars() == 64
    monkeypatch.delenv("TB_TRACE_EXEMPLARS")
    assert envcheck.trace_exemplars() == 32  # default


def test_tb_flight_ring_validated(monkeypatch):
    monkeypatch.setenv("TB_FLIGHT_RING", "big")
    with pytest.raises(envcheck.EnvVarError, match="TB_FLIGHT_RING"):
        envcheck.flight_ring()
    monkeypatch.setenv("TB_FLIGHT_RING", "0")
    with pytest.raises(envcheck.EnvVarError, match="must be >= 1"):
        envcheck.flight_ring()
    monkeypatch.setenv("TB_FLIGHT_RING", "128")
    assert envcheck.flight_ring() == 128
    monkeypatch.delenv("TB_FLIGHT_RING")
    assert envcheck.flight_ring() == 4096  # default


def test_tb_admit_queue_constraint_names_pipeline(monkeypatch):
    monkeypatch.setenv("TB_ADMIT_QUEUE", "soonish")
    with pytest.raises(envcheck.EnvVarError, match="TB_ADMIT_QUEUE"):
        envcheck.admit_queue(8)
    # Constraint: queue bound >= pipeline depth, named in the error.
    monkeypatch.setenv("TB_ADMIT_QUEUE", "4")
    with pytest.raises(
        envcheck.EnvVarError, match="pipeline depth \\(8\\)"
    ):
        envcheck.admit_queue(8)
    assert envcheck.admit_queue(4) == 4  # boundary is legal
    monkeypatch.setenv("TB_ADMIT_QUEUE", "16")
    assert envcheck.admit_queue(8) == 16
    monkeypatch.delenv("TB_ADMIT_QUEUE")
    assert envcheck.admit_queue(8) == 1024  # default


def test_tenant_qos_envs_validated(monkeypatch):
    monkeypatch.setenv("TB_TENANT_QOS", "2")
    with pytest.raises(envcheck.EnvVarError, match="TB_TENANT_QOS"):
        envcheck.tenant_qos()
    monkeypatch.delenv("TB_TENANT_QOS")
    assert envcheck.tenant_qos() == 1  # QoS on by default

    monkeypatch.setenv("TB_TENANT_RATE", "-1")
    with pytest.raises(envcheck.EnvVarError, match="TB_TENANT_RATE"):
        envcheck.tenant_rate()
    monkeypatch.delenv("TB_TENANT_RATE")
    assert envcheck.tenant_rate() == 0.0  # rate limit off by default

    monkeypatch.setenv("TB_BUSY_BACKOFF_MS", "nah")
    with pytest.raises(envcheck.EnvVarError, match="TB_BUSY_BACKOFF_MS"):
        envcheck.busy_backoff_ms()
    monkeypatch.setenv("TB_BUSY_BACKOFF_MS", "0")
    assert envcheck.busy_backoff_ms() == 0.0  # legacy immediate retry
    monkeypatch.delenv("TB_BUSY_BACKOFF_MS")
    assert envcheck.busy_backoff_ms() == 20.0


def test_tenant_queue_constraint_names_global_bound(monkeypatch):
    # A per-tenant bound above the global queue bound can never bind.
    monkeypatch.setenv("TB_TENANT_QUEUE", "100")
    with pytest.raises(
        envcheck.EnvVarError, match="TB_ADMIT_QUEUE \\(64\\)"
    ):
        envcheck.tenant_queue(64)
    monkeypatch.setenv("TB_TENANT_QUEUE", "16")
    assert envcheck.tenant_queue(64) == 16
    monkeypatch.delenv("TB_TENANT_QUEUE")
    # 0 (default) = the global bound: no extra per-tenant isolation.
    assert envcheck.tenant_queue(64) == 64


def test_tenant_weights_validated(monkeypatch):
    monkeypatch.setenv("TB_TENANT_WEIGHTS", "1:4, 7:2")
    assert envcheck.tenant_weights() == {1: 4.0, 7: 2.0}
    monkeypatch.setenv("TB_TENANT_WEIGHTS", "1:0")
    with pytest.raises(envcheck.EnvVarError, match="TB_TENANT_WEIGHTS"):
        envcheck.tenant_weights()
    monkeypatch.setenv("TB_TENANT_WEIGHTS", "banana")
    with pytest.raises(envcheck.EnvVarError, match="TB_TENANT_WEIGHTS"):
        envcheck.tenant_weights()
    monkeypatch.delenv("TB_TENANT_WEIGHTS")
    assert envcheck.tenant_weights() == {}


def test_tb_native_sanitize_validated(monkeypatch):
    monkeypatch.setenv("TB_NATIVE_SANITIZE", "msan")
    with pytest.raises(envcheck.EnvVarError, match="TB_NATIVE_SANITIZE"):
        envcheck.native_sanitize()
    monkeypatch.setenv("TB_NATIVE_SANITIZE", "asan")
    assert envcheck.native_sanitize() == "asan"
    monkeypatch.delenv("TB_NATIVE_SANITIZE")
    assert envcheck.native_sanitize() == ""  # default: release builds


def test_no_tb_knob_bypasses_envcheck():
    """Audit lint: every TB_*/BENCH_* knob in the package must be read
    through envcheck.py (validated, named errors), never via a raw
    os.environ / os.getenv call.  Round 17 migrated the r16 grep onto
    the tbcheck `envcheck` AST rule, which also resolves import
    aliases — ``from os import environ as E; E["TB_X"]`` no longer
    walks past the audit (proven by fixture in tests/test_tbcheck.py).
    """
    from tigerbeetle_tpu.analysis import run_lint
    from tigerbeetle_tpu.analysis.rules import EnvcheckRule

    result = run_lint(rules=[EnvcheckRule()])
    assert not result.findings, "\n".join(
        str(f) for f in result.findings
    )


def test_every_envcheck_reader_has_a_caller_in_the_package():
    """Audit: a public function of envcheck.py that nothing in the
    package calls is a knob kept alive for a script outside it.  AST
    walk over the package's sources (no import of the callers): a use
    is ``<alias of the envcheck module>.<name>`` or ``from
    tigerbeetle_tpu.envcheck import <name>``."""
    import ast
    import pathlib

    own = pathlib.Path(envcheck.__file__)
    # Not a reader: no variable behind it.  coord_timeout_s() calls it
    # for its named constraint, and the test of that constraint.
    exempt = {"view_change_budget_s"}
    readers = {
        node.name
        for node in ast.parse(own.read_text()).body
        if isinstance(node, ast.FunctionDef)
        and not node.name.startswith("_")
    } - exempt
    used = set()
    for path in own.parent.rglob("*.py"):
        if path == own:
            continue
        aliases, attrs = set(), set()
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.ImportFrom):
                if node.module == "tigerbeetle_tpu.envcheck":
                    used.update(a.name for a in node.names)
                elif node.module == "tigerbeetle_tpu":
                    aliases.update(
                        a.asname or a.name
                        for a in node.names
                        if a.name == "envcheck"
                    )
            elif isinstance(node, ast.Attribute) and isinstance(
                node.value, ast.Name
            ):
                attrs.add((node.value.id, node.attr))
        used.update(attr for name, attr in attrs if name in aliases)
    assert sorted(readers - used) == []


def test_tb_metrics_disables_histograms(monkeypatch):
    from tigerbeetle_tpu import obs

    monkeypatch.setenv("TB_METRICS", "0")
    reg = obs.Registry()
    hist = reg.histogram("x_us")
    hist.observe(12.0)  # no-op: nothing recorded, no clock reads
    assert hist.count == 0 and hist.percentile(0.99) == 0.0
    assert "x_us.count" not in reg.snapshot()
    # Counters stay live regardless of the knob.
    reg.counter("c").inc(3)
    assert reg.snapshot()["c"] == 3


def test_sharded_router_envs_validated(monkeypatch):
    monkeypatch.setenv("TB_ROUTER_QUEUE", "0")
    with pytest.raises(envcheck.EnvVarError, match="must be >= 1"):
        envcheck.router_queue()
    monkeypatch.setenv("TB_ROUTER_QUEUE", "512")
    assert envcheck.router_queue() == 512
    monkeypatch.delenv("TB_ROUTER_QUEUE")
    assert envcheck.router_queue() == 256

    monkeypatch.setenv("TB_COORD_RETRY_MS", "5")
    with pytest.raises(envcheck.EnvVarError, match="must be >= 10"):
        envcheck.coord_retry_ms()
    monkeypatch.delenv("TB_COORD_RETRY_MS")
    assert envcheck.coord_retry_ms() == 1000


def test_coord_timeout_names_view_change_constraint(monkeypatch):
    """The named constraint: the cross-shard hold timeout must exceed
    one shard's view-change budget, or a decided commit could expire
    under a primary failover on the credit shard."""
    budget = envcheck.view_change_budget_s()
    assert budget == 5.0  # VIEW_CHANGE_TICKS * TICK_NS
    monkeypatch.setenv("TB_COORD_TIMEOUT_S", "soon")
    with pytest.raises(envcheck.EnvVarError, match="TB_COORD_TIMEOUT_S"):
        envcheck.coord_timeout_s()
    monkeypatch.setenv("TB_COORD_TIMEOUT_S", "5")
    with pytest.raises(
        envcheck.EnvVarError, match="view-change budget \\(5s\\)"
    ):
        envcheck.coord_timeout_s()
    monkeypatch.setenv("TB_COORD_TIMEOUT_S", "6")
    assert envcheck.coord_timeout_s() == 6
    monkeypatch.delenv("TB_COORD_TIMEOUT_S")
    assert envcheck.coord_timeout_s() == 30  # default


def test_tb_state_commit_validated(monkeypatch):
    monkeypatch.setenv("TB_STATE_COMMIT", "maybe")
    with pytest.raises(envcheck.EnvVarError, match="TB_STATE_COMMIT"):
        envcheck.state_commit()
    monkeypatch.setenv("TB_STATE_COMMIT", "2")
    with pytest.raises(envcheck.EnvVarError, match="must be <= 1"):
        envcheck.state_commit()
    monkeypatch.setenv("TB_STATE_COMMIT", "0")
    assert envcheck.state_commit() == 0
    monkeypatch.delenv("TB_STATE_COMMIT")
    assert envcheck.state_commit() == 1  # default on


def test_tb_dev_scrub_fallback_validated(monkeypatch):
    monkeypatch.setenv("TB_DEV_SCRUB_FALLBACK", "often")
    with pytest.raises(envcheck.EnvVarError, match="TB_DEV_SCRUB_FALLBACK"):
        envcheck.scrub_fallback_every()
    monkeypatch.setenv("TB_DEV_SCRUB_FALLBACK", "-1")
    with pytest.raises(envcheck.EnvVarError, match="must be >= 0"):
        envcheck.scrub_fallback_every()
    monkeypatch.setenv("TB_DEV_SCRUB_FALLBACK", "4")
    assert envcheck.scrub_fallback_every() == 4
    monkeypatch.delenv("TB_DEV_SCRUB_FALLBACK")
    assert envcheck.scrub_fallback_every() == 0  # only on mismatch


def test_tb_metrics_disables_commitment_instruments(monkeypatch):
    """TB_METRICS=0: the commitment's latency histograms (digest
    update, cheap/fallback scrub split) become shared no-ops — a
    digest-update site costs one attribute check, no clock read —
    while the commit.* counters stay live (bench accounting reads
    them)."""
    from tigerbeetle_tpu import obs

    monkeypatch.setenv("TB_METRICS", "0")
    reg = obs.Registry()
    for name in ("commit.update_us", "scrub.cheap_us", "scrub.fallback_us"):
        hist = reg.histogram(name)
        hist.observe(5.0)
        assert hist.count == 0 and hist.percentile(0.5) == 0.0
        assert f"{name}.count" not in reg.snapshot()
    reg.counter("commit.updates").inc()
    reg.counter("commit.scrub_cheap").inc(2)
    snap = reg.snapshot()
    assert snap["commit.updates"] == 1
    assert snap["commit.scrub_cheap"] == 2


# ----------------------------------------------------------------------
# Root-attested follower serving (round 19).


def test_tb_root_ring_validated(monkeypatch):
    monkeypatch.setenv("TB_ROOT_RING", "many")
    with pytest.raises(envcheck.EnvVarError, match="TB_ROOT_RING"):
        envcheck.root_ring()
    monkeypatch.setenv("TB_ROOT_RING", "-1")
    with pytest.raises(envcheck.EnvVarError, match="must be >= 0"):
        envcheck.root_ring()
    monkeypatch.setenv("TB_ROOT_RING", "0")  # 0 = no at-op attestation
    assert envcheck.root_ring() == 0
    monkeypatch.delenv("TB_ROOT_RING")
    assert envcheck.root_ring() == 4096


def test_tb_read_policy_validated(monkeypatch):
    monkeypatch.setenv("TB_READ_POLICY", "maybe")
    with pytest.raises(envcheck.EnvVarError, match="TB_READ_POLICY"):
        envcheck.read_policy()
    for value in ("auto", "primary", "follower"):
        monkeypatch.setenv("TB_READ_POLICY", value)
        assert envcheck.read_policy() == value
    monkeypatch.delenv("TB_READ_POLICY")
    assert envcheck.read_policy() == "auto"


def test_tb_read_staleness_ops_validated(monkeypatch):
    monkeypatch.setenv("TB_READ_STALENESS_OPS", "fresh")
    with pytest.raises(envcheck.EnvVarError, match="TB_READ_STALENESS_OPS"):
        envcheck.read_staleness_ops()
    monkeypatch.setenv("TB_READ_STALENESS_OPS", "-1")
    with pytest.raises(envcheck.EnvVarError, match="must be >= 0"):
        envcheck.read_staleness_ops()
    monkeypatch.setenv("TB_READ_STALENESS_OPS", "0")  # fully caught up
    assert envcheck.read_staleness_ops() == 0
    monkeypatch.delenv("TB_READ_STALENESS_OPS")
    assert envcheck.read_staleness_ops() == 512


def test_tb_follower_attest_ms_validated(monkeypatch):
    monkeypatch.setenv("TB_FOLLOWER_ATTEST_MS", "0")
    with pytest.raises(envcheck.EnvVarError, match="must be >= 1"):
        envcheck.follower_attest_ms()
    monkeypatch.setenv("TB_FOLLOWER_ATTEST_MS", "250")
    assert envcheck.follower_attest_ms() == 250
    monkeypatch.delenv("TB_FOLLOWER_ATTEST_MS")
    assert envcheck.follower_attest_ms() == 100


def test_tb_follower_root_ring_named_constraint(monkeypatch):
    # Named constraint: < 16 discards the roots attestation needs
    # under write load.
    monkeypatch.setenv("TB_FOLLOWER_ROOT_RING", "8")
    with pytest.raises(envcheck.EnvVarError, match="must be >= 16"):
        envcheck.follower_ring()
    monkeypatch.setenv("TB_FOLLOWER_ROOT_RING", "64")
    assert envcheck.follower_ring() == 64
    monkeypatch.delenv("TB_FOLLOWER_ROOT_RING")
    assert envcheck.follower_ring() == 4096


def test_tb_read_fallback_ms_validated(monkeypatch):
    monkeypatch.setenv("TB_READ_FALLBACK_MS", "1")
    with pytest.raises(envcheck.EnvVarError, match="must be >= 10"):
        envcheck.read_fallback_ms()
    monkeypatch.setenv("TB_READ_FALLBACK_MS", "500")
    assert envcheck.read_fallback_ms() == 500
    monkeypatch.delenv("TB_READ_FALLBACK_MS")
    assert envcheck.read_fallback_ms() == 250


def test_tb_tenant_rate_bytes_validated(monkeypatch):
    monkeypatch.setenv("TB_TENANT_RATE_BYTES", "fast")
    with pytest.raises(envcheck.EnvVarError, match="TB_TENANT_RATE_BYTES"):
        envcheck.tenant_rate_bytes()
    monkeypatch.setenv("TB_TENANT_RATE_BYTES", "-5")
    with pytest.raises(envcheck.EnvVarError, match="must be >= 0"):
        envcheck.tenant_rate_bytes()
    monkeypatch.setenv("TB_TENANT_RATE_BYTES", "65536")
    assert envcheck.tenant_rate_bytes() == 65536.0
    monkeypatch.delenv("TB_TENANT_RATE_BYTES")
    assert envcheck.tenant_rate_bytes() == 0.0  # default off


def test_tb_follower_attest_max_ms_validated(monkeypatch):
    monkeypatch.setenv("TB_FOLLOWER_ATTEST_MAX_MS", "0")
    with pytest.raises(envcheck.EnvVarError, match="must be >= 1"):
        envcheck.follower_attest_max_ms()
    monkeypatch.setenv("TB_FOLLOWER_ATTEST_MAX_MS", "5000")
    assert envcheck.follower_attest_max_ms() == 5000
    monkeypatch.delenv("TB_FOLLOWER_ATTEST_MAX_MS")
    assert envcheck.follower_attest_max_ms() == 2000


def test_tb_hot_capacity_validated(monkeypatch):
    monkeypatch.setenv("TB_HOT_CAPACITY", "plenty")
    with pytest.raises(envcheck.EnvVarError, match="TB_HOT_CAPACITY"):
        envcheck.hot_capacity()
    monkeypatch.setenv("TB_HOT_CAPACITY", "-1")
    with pytest.raises(envcheck.EnvVarError, match="must be >= 0"):
        envcheck.hot_capacity()
    monkeypatch.setenv("TB_HOT_CAPACITY", str((1 << 31) + 1))
    with pytest.raises(envcheck.EnvVarError, match="must be <="):
        envcheck.hot_capacity()
    monkeypatch.setenv("TB_HOT_CAPACITY", "64")
    assert envcheck.hot_capacity() == 64
    monkeypatch.delenv("TB_HOT_CAPACITY")
    assert envcheck.hot_capacity() == 0  # default: all-resident


def test_tb_hot_capacity_gates_tiering(monkeypatch):
    """The knob is read at CONSTRUCTION through hot_tier.from_env —
    0/unset and budget >= capacity leave the table all-resident
    (today's behavior bit-for-bit); a small budget builds the tier."""
    from tigerbeetle_tpu.state_machine import hot_tier

    monkeypatch.delenv("TB_HOT_CAPACITY", raising=False)
    assert hot_tier.from_env(256) is None
    monkeypatch.setenv("TB_HOT_CAPACITY", "256")
    assert hot_tier.from_env(256) is None
    monkeypatch.setenv("TB_HOT_CAPACITY", "16")
    tier = hot_tier.from_env(256)
    assert tier is not None and tier.hot_rows == 16


def test_tb_native_pipeline_validated(monkeypatch):
    monkeypatch.setenv("TB_NATIVE_PIPELINE", "fast")
    with pytest.raises(envcheck.EnvVarError, match="TB_NATIVE_PIPELINE"):
        envcheck.native_pipeline()
    monkeypatch.setenv("TB_NATIVE_PIPELINE", "2")
    with pytest.raises(envcheck.EnvVarError, match="must be <= 1"):
        envcheck.native_pipeline()
    monkeypatch.setenv("TB_NATIVE_PIPELINE", "0")
    assert envcheck.native_pipeline() == 0
    monkeypatch.delenv("TB_NATIVE_PIPELINE")
    assert envcheck.native_pipeline() == 1  # default on


def test_tb_native_drain_validated(monkeypatch):
    monkeypatch.setenv("TB_NATIVE_DRAIN", "batch")
    with pytest.raises(envcheck.EnvVarError, match="TB_NATIVE_DRAIN"):
        envcheck.native_drain()
    monkeypatch.setenv("TB_NATIVE_DRAIN", "2")
    with pytest.raises(envcheck.EnvVarError, match="must be <= 1"):
        envcheck.native_drain()
    monkeypatch.setenv("TB_NATIVE_DRAIN", "-1")
    with pytest.raises(envcheck.EnvVarError, match="must be >= 0"):
        envcheck.native_drain()
    monkeypatch.setenv("TB_NATIVE_DRAIN", "0")
    assert envcheck.native_drain() == 0
    monkeypatch.delenv("TB_NATIVE_DRAIN")
    assert envcheck.native_drain() == 1  # default on


def test_tb_hash_reuse_validated(monkeypatch):
    monkeypatch.setenv("TB_HASH_REUSE", "yes")
    with pytest.raises(envcheck.EnvVarError, match="TB_HASH_REUSE"):
        envcheck.hash_reuse()
    monkeypatch.setenv("TB_HASH_REUSE", "2")
    with pytest.raises(envcheck.EnvVarError, match="must be <= 1"):
        envcheck.hash_reuse()
    monkeypatch.setenv("TB_HASH_REUSE", "-1")
    with pytest.raises(envcheck.EnvVarError, match="must be >= 0"):
        envcheck.hash_reuse()
    monkeypatch.setenv("TB_HASH_REUSE", "0")
    assert envcheck.hash_reuse() == 0
    monkeypatch.delenv("TB_HASH_REUSE")
    assert envcheck.hash_reuse() == 1  # default on


def test_tb_hash_threads_validated(monkeypatch):
    monkeypatch.setenv("TB_HASH_THREADS", "many")
    with pytest.raises(envcheck.EnvVarError, match="TB_HASH_THREADS"):
        envcheck.hash_threads()
    monkeypatch.setenv("TB_HASH_THREADS", "-1")
    with pytest.raises(envcheck.EnvVarError, match="must be >= 0"):
        envcheck.hash_threads()
    # The named constraint: lanes are capped at 16 — more than any
    # target box's cores only adds submit-path contention.
    monkeypatch.setenv("TB_HASH_THREADS", "17")
    with pytest.raises(envcheck.EnvVarError, match="must be <= 16"):
        envcheck.hash_threads()
    monkeypatch.setenv("TB_HASH_THREADS", "16")
    assert envcheck.hash_threads() == 16  # boundary accepted
    # Explicit 0 = inline hashing (no lanes), same as the default.
    monkeypatch.setenv("TB_HASH_THREADS", "0")
    assert envcheck.hash_threads() == 0
    monkeypatch.delenv("TB_HASH_THREADS")
    assert envcheck.hash_threads() == 0


def test_tb_native_drain_explicit_on_fails_fast_on_stale_so(monkeypatch):
    """TB_NATIVE_DRAIN=1 set EXPLICITLY against a loaded-but-stale
    library is a hard RuntimeError naming the rebuild (`make -C
    native`) at replica construction — the r20 stale-.so forensics
    extended to the r22 batch symbols.  (The defaulted knob degrades
    to the per-item arm; tests/test_native_drain.py covers that.)"""
    from tigerbeetle_tpu.runtime import fastpath

    class _Stale:
        tb_pl_abi_version = None

    monkeypatch.setattr(fastpath, "_load", lambda: _Stale())
    monkeypatch.setattr(fastpath, "_pipeline_warned", True)
    monkeypatch.delenv("TB_NATIVE_PIPELINE", raising=False)
    monkeypatch.setenv("TB_NATIVE_DRAIN", "1")
    err = fastpath.drain_error()
    assert err is not None and "make -C native" in err
    from tigerbeetle_tpu.testing.cluster import Cluster

    with pytest.raises(RuntimeError, match="make -C native"):
        Cluster(3, seed=1)


def test_tb_cpu_affinity_validated(monkeypatch):
    monkeypatch.delenv("TB_CPU_AFFINITY", raising=False)
    assert envcheck.cpu_affinity() == "none"  # default: no pinning
    monkeypatch.setenv("TB_CPU_AFFINITY", "auto")
    assert envcheck.cpu_affinity() == "auto"
    monkeypatch.setenv("TB_CPU_AFFINITY", "0,1,2")
    assert envcheck.cpu_affinity() == "0,1,2"
    monkeypatch.setenv("TB_CPU_AFFINITY", "zero")
    with pytest.raises(envcheck.EnvVarError, match="TB_CPU_AFFINITY"):
        envcheck.cpu_affinity()
    monkeypatch.setenv("TB_CPU_AFFINITY", "0,-1")
    with pytest.raises(envcheck.EnvVarError, match="must be >= 0"):
        envcheck.cpu_affinity()
    monkeypatch.setenv("TB_CPU_AFFINITY", "")
    assert envcheck.cpu_affinity() == "none"  # empty counts as unset


def test_affinity_plan_and_apply(monkeypatch):
    import os as _os

    from tigerbeetle_tpu.runtime import affinity

    assert affinity.plan(0, "none") is None
    ncpu = _os.cpu_count() or 1
    assert affinity.plan(3, "auto") == (3 % ncpu,)
    assert affinity.plan(0, "4,5") == (4,)
    assert affinity.plan(1, "4,5") == (5,)
    assert affinity.plan(2, "4,5") == (4,)  # wraps mod the list
    # apply() pins to a real core and reports it; spec from the env.
    monkeypatch.setenv("TB_CPU_AFFINITY", "auto")
    before = _os.sched_getaffinity(0)
    try:
        pinned = affinity.apply(slot=0)
        assert pinned == (0,)
        assert _os.sched_getaffinity(0) == {0}
    finally:
        _os.sched_setaffinity(0, before)
    # A planned core that does not exist on this box degrades to
    # unpinned (None), never to a failed spawn.
    assert affinity.apply(slot=0, spec="4096") is None
    assert _os.sched_getaffinity(0) == before
