"""One parser — and one inventory — for environment knobs.

Every on/off env toggle (TASKSRUNNER_ACCESS_LOG, TASKSRUNNER_FLASH,
TASKSRUNNER_PERF_TESTS, ...) must accept the same spellings — a
per-call-site tuple would drift the moment one copy learns a new
spelling.

:data:`FLAGS` is the central inventory of every ``TASKSRUNNER_*``
variable the runtime reads: name, kind, default, one-line doc. The
``env-flag-discipline`` tasklint rule fails the build on any raw
``os.environ`` read of a declared boolean (must use :func:`env_flag`)
and on any undeclared ``TASKSRUNNER_*`` read; ``env_flag`` itself
refuses undeclared names at runtime, and ``tests/test_flag_inventory``
asserts the inventory and the docs agree.
"""

from __future__ import annotations

import dataclasses
import os

_FALSE = frozenset({"0", "false", "off", "no"})


@dataclasses.dataclass(frozen=True)
class Flag:
    """One declared environment variable."""

    name: str
    kind: str      # "bool" | "int" | "float" | "string" | "path" | "enum" | "json"
    default: str   # human-readable default ("on"/"off" for bools)
    doc: str
    #: the value is a credential: the tasklint secret-taint rule treats
    #: env reads of it as taint sources (never logged unredacted)
    secret: bool = False


def _f(name: str, kind: str, default: str, doc: str,
       *, secret: bool = False) -> tuple[str, Flag]:
    return name, Flag(name, kind, default, doc, secret)


#: every TASKSRUNNER_* variable any part of the repo reads. Keep the
#: table alphabetical; the docs table in module 31 must list every name
#: here (asserted by tests/test_flag_inventory.py).
FLAGS: dict[str, Flag] = dict([
    _f("TASKSRUNNER_ACCESS_LOG", "bool", "on",
       "per-request access-log lines from app servers and sidecars"),
    _f("TASKSRUNNER_ACTORS", "bool", "off",
       "virtual-actor runtime (placement, turns, reminders, failover)"),
    _f("TASKSRUNNER_ACTOR_LEASE_SECONDS", "float", "30",
       "placement lease duration; expiry lets survivors take ownership"),
    _f("TASKSRUNNER_ACTOR_REMINDER_POLL_SECONDS", "float", "2",
       "reminder/lease sweep interval on every actor-hosting replica"),
    _f("TASKSRUNNER_ACTOR_TURN_TIMEOUT_SECONDS", "float", "30",
       "per-turn actor handler deadline before the turn fails"),
    _f("TASKSRUNNER_ACT_F32", "bool", "off",
       "keep ML activations in float32 instead of the platform default"),
    _f("TASKSRUNNER_ADMISSION", "bool", "off",
       "per-replica admission control (shed with 429 when saturated)"),
    _f("TASKSRUNNER_ADMISSION_MAX_INFLIGHT", "int", "64",
       "in-flight app requests at which the saturation score reaches 1.0"),
    _f("TASKSRUNNER_ADMISSION_MAX_LAG_SECONDS", "float", "0.25",
       "event-loop lag at which the saturation score reaches 1.0"),
    _f("TASKSRUNNER_ADMISSION_MAX_QUEUE_DEPTH", "int", "512",
       "state/broker write-queue depth at which the score reaches 1.0"),
    _f("TASKSRUNNER_API_TOKEN", "string", "unset",
       "bearer token the sidecar and admin APIs require when set",
       secret=True),
    _f("TASKSRUNNER_APP_ID", "string", "unset",
       "app-id grants are evaluated against (injected by the orchestrator)"),
    _f("TASKSRUNNER_CHAOS", "bool", "off",
       "master gate for declarative fault injection (kind: Chaos)"),
    _f("TASKSRUNNER_FLASH", "bool", "on",
       "flash-attention path in the ML extension"),
    _f("TASKSRUNNER_FLASH_BWD_DELTA", "enum", "precompute",
       "flash backward delta strategy (precompute | fused)"),
    _f("TASKSRUNNER_FLASH_HBLK_BWD", "int", "auto",
       "head-block size override for the flash backward kernel"),
    _f("TASKSRUNNER_FLASH_HBLK_FWD", "int", "auto",
       "head-block size override for the flash forward kernel"),
    _f("TASKSRUNNER_FLASH_HBLK_RING", "int", "auto",
       "head-block size override for the ring-attention kernel"),
    _f("TASKSRUNNER_FLIGHTREC", "bool", "on",
       "black-box flight recorder (ring of recent request timelines, "
       "dumped on shed entry, slow exemplars, and unclean shutdown)"),
    _f("TASKSRUNNER_FLIGHTREC_DIR", "path", ".tasksrunner/flightrec",
       "directory flight-recorder dumps are written to"),
    _f("TASKSRUNNER_FLIGHTREC_RING", "int", "256",
       "request timelines the flight-recorder ring retains per process"),
    _f("TASKSRUNNER_GRANTS", "json", "unset",
       "JSON grants document applied to the app (orchestrator-injected)"),
    _f("TASKSRUNNER_HISTOGRAMS", "bool", "on",
       "latency-histogram recording kill switch"),
    _f("TASKSRUNNER_HTTP_PORT", "int", "3500",
       "sidecar port AppClient.from_env connects to"),
    _f("TASKSRUNNER_MESH", "bool", "on",
       "framed sidecar-to-sidecar transport lane"),
    _f("TASKSRUNNER_MESH_CA", "path", "unset",
       "CA bundle path; with CERT and KEY enables mesh mTLS"),
    _f("TASKSRUNNER_MESH_CERT", "path", "unset",
       "mesh mTLS certificate path"),
    _f("TASKSRUNNER_MESH_COALESCE", "bool", "on",
       "write-behind frame coalescing (off = per-frame write+drain)"),
    _f("TASKSRUNNER_MESH_COALESCE_SECONDS", "float", "0",
       "extra coalescing window per flush (0 = event-loop-natural batching)"),
    _f("TASKSRUNNER_MESH_CODEC", "enum", "binary",
       "mesh header codec ceiling (binary | json); json forces the v1 headers"),
    _f("TASKSRUNNER_MESH_CONNECT_TIMEOUT_SECONDS", "float", "2",
       "mesh dial deadline before the caller falls back to HTTP"),
    _f("TASKSRUNNER_MESH_KEY", "path", "unset",
       "mesh mTLS private-key path"),
    _f("TASKSRUNNER_MESH_PING_SECONDS", "float", "15",
       "pre-warm/keepalive tick: idle-ping cadence (<= 0 disables)"),
    _f("TASKSRUNNER_MESH_REQUEST_TIMEOUT_SECONDS", "float", "300",
       "per-request mesh ceiling; consecutive expiries condemn the connection"),
    _f("TASKSRUNNER_ML_BATCHING", "bool", "on",
       "continuous micro-batching in the ML serving plane (off = batch-of-one)"),
    _f("TASKSRUNNER_ML_BUCKETS", "string", "1,2,4,8,16,32",
       "padding-bucket ladder; each bucket jit-compiles exactly once at warmup"),
    _f("TASKSRUNNER_ML_MAX_BATCH", "int", "32",
       "micro-batch size that flushes assembly immediately (size flush)"),
    _f("TASKSRUNNER_ML_MAX_DELAY_MS", "float", "5",
       "micro-batch assembly latency budget before a partial batch flushes"),
    _f("TASKSRUNNER_ML_MAX_QUEUE", "int", "256",
       "queued inference requests beyond which submits shed with 429"),
    _f("TASKSRUNNER_ML_MAX_TOKENS", "int", "8192",
       "tokens in flight at which the ML admission signal reaches 1.0"),
    _f("TASKSRUNNER_PERF_TESTS", "bool", "off",
       "opt-in performance assertions in the test suite"),
    _f("TASKSRUNNER_REPLICA", "int", "0",
       "replica index injected by the orchestrator"),
    _f("TASKSRUNNER_REPL_ACK_TIMEOUT_SECONDS", "float", "10",
       "deadline for a write to reach its ack quorum before failing 503"),
    _f("TASKSRUNNER_REPL_LEASE_SECONDS", "float", "5",
       "shard-leadership lease duration; expiry lets a follower promote"),
    _f("TASKSRUNNER_REPL_LOG_RETAIN", "int", "4096",
       "replication records kept per member; gaps beyond resync via snapshot"),
    _f("TASKSRUNNER_REPL_MAX_LAG_RECORDS", "int", "256",
       "follower lag bound for stale-tolerant reads (followerReads)"),
    _f("TASKSRUNNER_RESHARD", "bool", "off",
       "orchestrator elastic-placement control loop (heat ranking + "
       "rebalance planning over sharded stores)"),
    _f("TASKSRUNNER_RESHARD_HEAT_THRESHOLD", "float", "50",
       "EWMA write rate (ops/s) above which a shard counts as hot"),
    _f("TASKSRUNNER_RESHARD_HYSTERESIS_SECONDS", "float", "10",
       "how long a shard must stay above the heat threshold before it "
       "ranks hot (spikes below this never trigger a rebalance)"),
    _f("TASKSRUNNER_RESHARD_PAUSE_BUDGET_SECONDS", "float", "2",
       "write-pause ceiling for the fenced routing flip; a measured "
       "pause beyond it logs a warning with the drain time"),
    _f("TASKSRUNNER_SLOW_THRESHOLD_SECONDS", "float", "0.25",
       "latency above which histogram observations capture trace exemplars"),
    _f("TASKSRUNNER_SOAK", "bool", "off",
       "opt-in long-running soak tests"),
    _f("TASKSRUNNER_TOKENS_FILE", "path", "unset",
       "per-app API-token table used by the orchestrator"),
    _f("TASKSRUNNER_TRACE_DB", "path", ".tasksrunner/traces.db",
       "span-recorder SQLite path (set empty to disable recording)"),
    _f("TASKSRUNNER_TRACE_RETENTION_SECONDS", "float", "2592000",
       "span retention sweep horizon in seconds (<= 0 keeps everything)"),
    _f("TASKSRUNNER_UVLOOP", "bool", "off",
       "install uvloop's event-loop policy when the package is available"),
    _f("TASKSRUNNER_WORKFLOWS", "bool", "off",
       "durable workflow engine (orchestrators, activities, sagas) on "
       "the actor runtime"),
    _f("TASKSRUNNER_WORKFLOW_ACTIVITY_TIMEOUT_SECONDS", "float", "30",
       "default per-attempt activity deadline when the activity "
       "declares none"),
    _f("TASKSRUNNER_WORKFLOW_HISTORY_RETAIN_SECONDS", "float", "3600",
       "how long a terminal instance keeps its full history before the "
       "GC reminder truncates it to a summary (<= 0 keeps everything)"),
    _f("TASKSRUNNER_WORKFLOW_REPLAY_BATCH", "int", "16",
       "max activity executions committed per workflow step turn; "
       "bounds both turn length and the work a crash can lose"),
])

#: names env_flag accepts — the env-flag-discipline rule sends every
#: raw os.environ read of these through here
BOOL_FLAGS = frozenset(n for n, f in FLAGS.items() if f.kind == "bool")


def env_flag(name: str, default: bool = True) -> bool:
    """True unless the variable is set to an explicit disable value
    (case-insensitive: 0 / false / off / no). Unset or empty →
    ``default``.

    ``TASKSRUNNER_*`` names must be declared in :data:`FLAGS` — an
    undeclared knob is invisible to operators, the docs, and the
    static analysis, so it is refused loudly here rather than parsed
    quietly.
    """
    if name.startswith("TASKSRUNNER_") and name not in FLAGS:
        raise LookupError(
            f"{name} is not declared in tasksrunner.envflag.FLAGS — "
            "add it to the inventory (name, kind, default, doc)")
    raw = os.environ.get(name)
    if raw is None or not raw.strip():
        return default
    return raw.strip().lower() not in _FALSE
