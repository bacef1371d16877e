"""Test fixture: force an 8-device virtual CPU mesh before jax initialises.

The reference tests distribution via multi-partition local Spark
(``local[1]`` + ``makeRDD(..., 2)`` — SURVEY.md §4); our analog is jax's
virtual CPU devices, so every multi-device code path (shard_map, psum,
collectives) runs in CI without TPU hardware.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# The block-parallel device pool (ops/device_pool.py) would engage by
# default on this 8-device test mesh and dispatch every multi-block map
# verb across all 8 virtual devices — one executable (and one program
# trace) PER DEVICE, which breaks the suite's trace/compile-count fences
# (test_bucketing, test_observability) and makes span stats
# nondeterministic.  The main suite therefore pins the single-device
# baseline; the device-pool tests (tests/test_device_pool.py) re-enable
# the pool explicitly per test, and run process-isolated below.
os.environ.setdefault("TFS_DEVICE_POOL", "0")

# Block-level fault tolerance (ops/fault_tolerance.py) stays OFF in the
# main suite: retries re-dispatch blocks (extra traces would break the
# trace/compile-count fences) and fault injection is chaos by design.
# The fault-tolerance tests (tests/test_fault_tolerance.py) re-enable
# both explicitly per test; run_tests.sh's chaos tier runs them under
# TFS_FAULT_INJECT matrices.
os.environ.setdefault("TFS_BLOCK_RETRIES", "0")
os.environ.setdefault("TFS_FAULT_INJECT", "")

# Bridge serving resilience (round 11, bridge/server.py) stays OFF in the
# main suite: the admission gate would serialize/shed concurrent test
# servers' verbs, and per-session frame caps are policy under test, not
# test infrastructure.  The bridge-resilience tests pass their knobs as
# explicit BridgeServer constructor params (and set TFS_FAULT_INJECT
# per-test via monkeypatch), so the process env stays at the
# deterministic round-7 trace-fence baseline; run_tests.sh's bridge tier
# re-runs them process-isolated.
os.environ.setdefault("TFS_BRIDGE_MAX_INFLIGHT", "0")
os.environ.setdefault("TFS_BRIDGE_QUEUE_DEPTH", "16")
os.environ.setdefault("TFS_BRIDGE_MAX_FRAMES", "0")
# ...and the CLIENT knobs.  Like every TFS_* default above these are
# absence-defaults (setdefault), not hard pins: an explicitly exported
# value — e.g. run_tests.sh's bridge tier, or a developer reproducing a
# timeout-sensitive failure — deliberately wins over the suite baseline.
os.environ.setdefault("TFS_BRIDGE_CLIENT_TIMEOUT_S", "")
os.environ.setdefault("TFS_BRIDGE_CLIENT_RETRIES", "3")

# Out-of-core streaming (round 12, tensorframes_tpu/streaming/) stays at
# its inert defaults in the main suite: no spill dir (evictions drop to
# the authoritative host copy as rounds 10-11 pinned), no host-budget
# window clamp, default window size.  The streaming tests set their own
# knobs via monkeypatch/tmp_path; run_tests.sh's streaming tier re-runs
# them with the env knobs live.  Like every TFS_* default above these
# are absence-defaults (setdefault), not hard pins: an explicitly
# exported TFS_SPILL_DIR/TFS_HOST_BUDGET — e.g. the streaming tier, or
# a developer reproducing a spill-path failure — deliberately wins.
os.environ.setdefault("TFS_SPILL_DIR", "")
os.environ.setdefault("TFS_HOST_BUDGET", "")
os.environ.setdefault("TFS_STREAM_WINDOW", "")
os.environ.setdefault("TFS_STREAM_BLOCKS", "")

# Observability (round 13): the flight recorder and the HTTP metrics
# endpoint stay OFF in the main suite — trace events are process-global
# ring-buffer state recorded at block granularity, and a port-bound
# endpoint is serving infrastructure, not test infrastructure.  Tests
# drive the recorder through the API (observability.enable_trace()
# overrides the env); run_tests.sh's observability tier re-runs the
# trace/metrics tests with TFS_TRACE=1 exported, which wins over these
# absence-defaults like every other tier's knobs.  The always-on latency
# histograms need no pin: they never trace, compile, or dispatch.
os.environ.setdefault("TFS_TRACE", "0")
os.environ.setdefault("TFS_TRACE_EVENTS", "")
os.environ.setdefault("TFS_METRICS_PORT", "")

# Request-scoped telemetry (round 15): the slow-request structured log
# stays OFF in the main suite (a log line per test request is noise and
# some tests assert on captured logs), and the tenant-label cap keeps
# its default.  Absence-defaults like every TFS_* pin above: the
# attribution tier (run_tests.sh) exports TFS_SLOW_REQUEST_MS live, and
# tests drive thresholds via monkeypatch.  The ledger layer itself
# needs no pin — with no active request it is one contextvar read.
os.environ.setdefault("TFS_SLOW_REQUEST_MS", "")
os.environ.setdefault("TFS_TENANT_LABELS", "")

# Multi-tenant serving throughput layer (round 16, bridge/coalescer.py)
# stays OFF in the main suite: coalescing merges concurrent requests
# into shared dispatches (changing trace/compile counts and span stats
# the fences pin), the warm program pool reuses Program objects across
# requests (same effect), and the SLO scheduler sheds by policy.  The
# coalescer tests pass explicit BridgeServer constructor params;
# run_tests.sh's serving tier re-runs them with the env knobs live.
# Absence-defaults (setdefault), not hard pins, like every TFS_* above.
os.environ.setdefault("TFS_BRIDGE_COALESCE_US", "")
os.environ.setdefault("TFS_BRIDGE_COALESCE_ROWS", "")
os.environ.setdefault("TFS_BRIDGE_WARM", "")
os.environ.setdefault("TFS_BRIDGE_FAIR_ROWS", "")
os.environ.setdefault("TFS_BRIDGE_FAIR_WINDOW_S", "")
os.environ.setdefault("TFS_BRIDGE_SLO_MS", "")
os.environ.setdefault("TFS_BRIDGE_CLIENT_BUSY_RETRIES", "")

# Lazy verb-graph planner (round 14, ops/planner.py) stays OFF in the
# main suite: with TFS_PLAN=1 every module-level map verb returns a
# LazyFrame and defers dispatch, which would change when (and how many
# times) programs trace — breaking the suite's trace/compile-count
# fences that pin the eager baseline.  The planner tests opt in
# explicitly (frame.lazy() / monkeypatch); run_tests.sh's planner tier
# re-runs them with TFS_PLAN=1 exported, which wins over this
# absence-default like every other tier's knobs.
os.environ.setdefault("TFS_PLAN", "0")
# Planner v2 (round 19): the cross-plan CSE registry's absence default
# is ON — it only engages inside planned executions, which the main
# suite opts into per test; the measured-calibration feedback and the
# per-tenant HBM cache cap default OFF/uncapped.  The planner-v2 tests
# drive all three via monkeypatch; the planner tier re-runs them with
# the knobs exported live.
os.environ.setdefault("TFS_PLAN_CSE", "")
os.environ.setdefault("TFS_PLAN_CALIBRATE", "")
os.environ.setdefault("TFS_CACHE_TENANT_BUDGET", "")

# Relational verbs (round 18, tensorframes_tpu/relational/): shuffle,
# windowed joins, and bridge pipelines stay at their inert defaults in
# the main suite — shuffle needs TFS_SPILL_DIR (pinned empty above), so
# relational tests pass explicit spill stores / monkeypatch; the
# run_tests.sh relational tier re-runs them with the TFS_SHUFFLE_* /
# TFS_JOIN_* knobs live.  TFS_RELEASE_HOST's absence default is AUTO
# (release a windowed frame's host columns once a spill-backed sharded
# cache covers them) — deterministic, so no off-pin is needed.
os.environ.setdefault("TFS_SHUFFLE_PARTITIONS", "")
os.environ.setdefault("TFS_JOIN_BROADCAST_BYTES", "")
os.environ.setdefault("TFS_RELEASE_HOST", "")
# absence default = NO filesystem roots allowed to the bridge pipeline
# RPC's path-based sources/sinks; bridge tests allow their tmp dirs
os.environ.setdefault("TFS_BRIDGE_PIPELINE_PATHS", "")

# Durable execution (round 20, tensorframes_tpu/recovery/): the job
# journal stays OFF in the main suite — journaling adds disk writes at
# every window boundary and verbs only consult it when a job_id= is
# passed, but the knob must still be pinned so a developer's exported
# TFS_JOURNAL_DIR cannot silently make suite streams durable.  The
# recovery tests pass tmp_path journals via monkeypatch; run_tests.sh's
# recovery tier re-runs them with the knob live (and drives the
# proc_kill subprocess harness).  Absence-default like every TFS_* pin.
os.environ.setdefault("TFS_JOURNAL_DIR", "")

# Static program analysis (round 17, tensorframes_tpu/analysis/): the
# classifier itself is deterministic and its traces are suppressed from
# the retrace counters, so it stays ON (empty = absence default = on) —
# the bit-identity contract is that analyzer-on equals analyzer-off.
# The differential xcheck mode stays OFF in the main suite (it doubles
# probe work); run_tests.sh's lint tier re-runs the analysis corpus
# with TFS_ANALYZE_XCHECK=1 exported, which wins over these
# absence-defaults like every other tier's knobs.
os.environ.setdefault("TFS_ANALYZE", "")
os.environ.setdefault("TFS_ANALYZE_XCHECK", "")

# Bridge fleet (round 21, tensorframes_tpu/bridge/fleet.py): no fleet
# in the main suite — no registry dir (heartbeat files off), no replica
# identity override, router knobs at their documented defaults.  The
# fleet tests build routers/fleets with explicit constructor args;
# run_tests.sh's fleet tier re-runs them with the registry + shared
# journal/compile-cache dirs live (multi-process replicas, chaos leg).
os.environ.setdefault("TFS_FLEET_SIZE", "")         # no ambient fleet size
os.environ.setdefault("TFS_FLEET_REGISTRY", "")     # heartbeats off
os.environ.setdefault("TFS_FLEET_REPLICA", "")      # no identity override
os.environ.setdefault("TFS_FLEET_HEALTH_S", "")     # poll period: default
os.environ.setdefault("TFS_FLEET_QUARANTINE_AFTER", "")  # flap threshold
os.environ.setdefault("TFS_FLEET_QUARANTINE_S", "")      # hold: default
# busy-retry hint cap (round 21): default cap, jitter unaffected
os.environ.setdefault("TFS_BRIDGE_CLIENT_BUSY_CAP_MS", "")

# Paged continuous decode (round 22, models/kv_pager.py + the bridge
# DecodeScheduler): page size and slot count at their documented
# defaults (16 tokens/page, 8 slots) in the main suite — the paged
# tests size pools/pages explicitly via constructor args so the
# bit-identity and refusal contracts are deterministic regardless of a
# developer's exported knobs.  run_tests.sh's decode tier re-runs them
# with the knobs live in a forced-8-device child.  Absence-defaults
# (setdefault) like every TFS_* pin above.
os.environ.setdefault("TFS_DECODE_PAGE_TOKENS", "")
os.environ.setdefault("TFS_DECODE_MAX_SLOTS", "")

# Absence-default pins for every remaining TFS_* knob the package reads
# (round 17; enforced by tools/tfs_lint.py rule `knob-pins`).  Each pin
# is the knob's documented "unset" behavior — setdefault, so an
# explicitly exported value (a run_tests.sh tier, a developer repro)
# deliberately wins.  Pinning the complete inventory means a NEW knob
# cannot silently change the main suite's deterministic baseline: the
# lint fails until the knob is pinned here and documented.
for _knob in (
    "TFS_BLOCK_BACKOFF_S",     # retry backoff: default schedule
    "TFS_BLOCK_BUCKETS",       # bucketing: default power-of-two policy
    "TFS_BRIDGE_DRAIN_S",      # bridge drain grace: default
    "TFS_BRIDGE_SESSION_TTL_S",  # session TTL: default
    "TFS_BRIDGE_MAX_MESSAGE_BYTES",  # wire caps: defaults
    "TFS_BRIDGE_MAX_BINARY_BYTES",
    "TFS_CACHE_SHARDED",       # "" == auto (pool-following) sharding
    "TFS_COMPILE_CACHE",       # no persistent compile cache
    "TFS_DONATE",              # "" == auto (backend-dependent) donation
    "TFS_HBM_BUDGET",          # unlimited resident-shard budget
    "TFS_MIN_SPLIT_ROWS",      # OOM-split floor: default
    "TFS_PLAN_POOL_MIN_INTENSITY",  # planner pool threshold: default
    "TFS_PREFETCH_BLOCKS",     # staging window: default depth
    "TFS_QUARANTINE_AFTER",    # quarantine threshold: default
    "TFS_STREAM_CHUNK_BYTES",  # h2d chunking: default 64M
):
    os.environ.setdefault(_knob, "")

import jax  # noqa: E402

# The reference computes in float64 by default (python floats -> Double,
# datatypes.scala:328-387).  Enable x64 on the CPU test mesh so dtype-fidelity
# tests exercise the full registry; TPU runs use f32/bf16 regardless.
jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs


# ---------------------------------------------------------------------------
# GSPMD-fragile test auto-isolation (round 6, VERDICT r5 weak #4)
# ---------------------------------------------------------------------------
#
# XLA:CPU's collective runtime carries process-global state that, after
# several hundred shard_map/GSPMD tests in one process, can abort natively
# (SIGABRT, no Python traceback) on an otherwise-correct program — observed
# as an order-dependent crash of ``test_1f1b_composes_with_gspmd_sp`` at
# ~85% of the full suite (VERDICT r4 weak #1) while the same test passes in
# isolation.  Like the documented 1F1B x tp collective-schedule deadlock
# (``train.loss_and_grad_1f1b``) and the cond-skipped-collective rendezvous
# hang (``train.pipelined_blocks``), this is upstream XLA:CPU runtime
# fragility, not a framework bug: real TPU jobs get one fresh runtime per
# process, which is exactly what the isolation reproduces for the test.
#
# Round 5 isolated the one observed victim via a hand-applied decorator
# (``tests/_isolate.py``); this conftest replaces the hand list with
# detection *by construction*: every collected test whose source touches a
# mesh / shard_map surface is marked ``mesh``, and the subset that drives
# manual collectives (ppermute rings, the pipeline schedules) — the class
# every observed crash belongs to — is marked ``gspmd_isolated`` and runs
# in its own interpreter automatically.  A new pipeline/ring test gets the
# same treatment without editing any list.
#
# Isolated tests re-invoke themselves under a fresh ``pytest`` process
# (``TFS_TEST_ISOLATED=1`` breaks the recursion) and assert the child's
# exit status.  Native deaths (SIGABRT/SIGSEGV-class rcs) are retried —
# the rendezvous race is timing-dependent (15-50% firing rate under load,
# 0% on a quiet box), so a crashed attempt says nothing about the numerics
# the test pins.  An ORDINARY assertion failure (rc=1) is deterministic
# and fails immediately; retrying it would mask real regressions.
#
# Knobs: ``TFS_ISOLATE=0`` disables the subprocess hop (debugging inside
# one process); ``TFS_ISOLATE=all`` widens it to every ``mesh``-marked
# test (slow; a reproduction tool, not the CI default).

import functools  # noqa: E402
import inspect  # noqa: E402
import re  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

_ISOLATED_ENV = "TFS_TEST_ISOLATED"

# any mesh/shard_map surface: these tests exercise the multi-device runtime
_MESH_PAT = re.compile(
    r"shard_map|make_mesh|set_mesh|training_mesh|mesh_executor|MeshExecutor"
)
# the fragile subclass: manual collectives (ring ppermutes, the pipeline
# schedules) inside shard_map — every observed native crash is in this class
_FRAGILE_PAT = re.compile(r"ppermute|1f1b|pipelined|pipeline_schedule")
# device-pool dispatch tests (tests/test_device_pool.py, names
# ``test_pooled_*``): each spawns its own interpreter on the forced
# 8-device CPU mesh, so pool scheduling (multi-device jit caches, staged
# lanes, env-knob flips) never leaks compiled-per-device state or timing
# interference into the single-device-pinned main suite
_POOL_PAT = re.compile(r"test_pooled_")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "mesh: auto-applied to tests whose source uses mesh/shard_map "
        "surfaces (select with -m mesh)",
    )
    config.addinivalue_line(
        "markers",
        "gspmd_isolated: auto-applied to mesh tests driving manual "
        "collectives; each runs in its own interpreter (fresh XLA:CPU "
        "runtime) with native-death-only retries",
    )
    config.addinivalue_line(
        "markers",
        "slow: long-running tests (the subprocess-heavy recovery "
        "kill matrix); tier-1 runs -m 'not slow', the recovery tier "
        "runs them all",
    )
    config.addinivalue_line(
        "markers",
        "pool_isolated: auto-applied to device-pool dispatch tests "
        "(test_pooled_*); each runs in its own interpreter under the "
        "forced 8-device XLA_FLAGS so multi-device scheduling never "
        "shares a process with the single-device-pinned main suite",
    )


def _item_source(item) -> str:
    fn = getattr(item, "function", None)
    if fn is None:
        return ""
    try:
        return inspect.getsource(fn)
    except (OSError, TypeError):
        return ""


def _run_in_subprocess(
    nodeid: str, rootpath: str, attempts: int = 4, extra_env=None
):
    proc = None
    for attempt in range(attempts):
        proc = subprocess.run(
            [
                sys.executable,
                "-m",
                "pytest",
                nodeid,
                "-q",
                "-x",
                "-p",
                "no:cacheprovider",
            ],
            cwd=rootpath,
            env={**os.environ, _ISOLATED_ENV: "1", **(extra_env or {})},
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            timeout=600,
        )
        if proc.returncode == 0:
            return
        # deterministic pytest outcomes fail fast — only native deaths
        # (signal rcs) are the timing-dependent class worth retrying:
        # 1 = test failure, 2 = interrupted/collection error, 4 = usage
        # error, 5 = no tests collected
        if proc.returncode in (1, 2, 4, 5):
            break
    raise AssertionError(
        f"isolated test {nodeid} failed in its subprocess "
        f"(rc={proc.returncode}, {attempt + 1}/{attempts} attempts):\n"
        f"{proc.stdout[-8000:]}"
    )


def _isolate_item(item, extra_env=None) -> None:
    inner = item.obj
    nodeid = item.nodeid
    rootpath = str(item.config.rootpath)

    @functools.wraps(inner)
    def wrapper(*args, **kwargs):
        if os.environ.get(_ISOLATED_ENV) == "1":
            return inner(*args, **kwargs)
        _run_in_subprocess(nodeid, rootpath, extra_env=extra_env)

    item.obj = wrapper


def _pool_test_env() -> dict:
    """Env for an isolated device-pool test child: the forced 8-device
    CPU mesh, pinned explicitly (belt and braces — the child's conftest
    sets the same flags, but the child must see them even if invoked
    with a caller-tweaked XLA_FLAGS)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = (flags + " --xla_force_host_platform_device_count=8").strip()
    return {"XLA_FLAGS": flags, "JAX_PLATFORMS": "cpu"}


def pytest_collection_modifyitems(config, items):
    isolate_mode = os.environ.get("TFS_ISOLATE", "")
    for item in items:
        if _POOL_PAT.search(item.name):
            item.add_marker(pytest.mark.pool_isolated)
            if isolate_mode != "0":
                _isolate_item(item, extra_env=_pool_test_env())
            continue
        src = _item_source(item)
        fixtures = set(getattr(item, "fixturenames", ()))
        uses_mesh = bool(_MESH_PAT.search(src)) or "devices" in fixtures
        if not uses_mesh:
            continue
        item.add_marker(pytest.mark.mesh)
        fragile = bool(_FRAGILE_PAT.search(src)) or isolate_mode == "all"
        if fragile and isolate_mode != "0":
            item.add_marker(pytest.mark.gspmd_isolated)
            _isolate_item(item)
