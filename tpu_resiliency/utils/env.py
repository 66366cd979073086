"""Typed registry of every ``TPURX_*`` environment knob.

Seven PRs accreted ~50 knobs, each read site re-deciding its own default and
parse convention (``!= "0"`` here, ``== "1"`` there, ``or 0`` for empty
strings somewhere else) — and two sites disagreeing about the default store
port.  This module is the single home: every knob is declared once with a
name, type, default, and doc line; every library read routes through
``Knob.get()`` (enforced by tpurx-lint rule TPURX010); and
``docs/configuration.md`` is generated from the declarations
(``python -m tpu_resiliency.utils.env --write``).

Parse conventions (uniform for every knob):

- empty string == unset (falls back to the declared default);
- bool: ``0 / false / no / off`` (case-insensitive) are False, anything else
  set is True;
- a knob may name a ``fallback`` env var (e.g. ``TPURX_RANK`` falls back to
  plain ``RANK``) consulted when the primary is unset;
- ``Knob.get(default=...)`` overrides the declared default for call sites
  whose default is computed (e.g. the beater CPU pin).

This module must import nothing from the package (everything imports it).
"""

from __future__ import annotations

import os
import threading

_UNSET = object()
_BOOL_FALSE = frozenset({"0", "false", "no", "off"})

_REGISTRY: dict = {}

# Runtime-override layer: the adaptive policy engine retunes knobs mid-run
# (save cadence, replication factor, rung selection) WITHOUT mutating
# os.environ — env mutation leaks into child processes, races exec'd
# monitors, and is banned by lint rule TPURX010.  Overrides sit in front of
# the environment for Knob.raw(); the only sanctioned writer is the policy
# actuator layer (tpu_resiliency/policy/actuator.py).
_OVERRIDES: dict = {}
_OVERRIDES_LOCK = threading.Lock()


def set_runtime_override(name: str, value) -> None:
    """Install a runtime value for a declared knob (string-formatted, parsed
    by the knob's declared type on read).  ``None`` clears the override.
    Raises KeyError for undeclared names — a typo'd override must fail
    loudly, exactly like a typo'd knob read."""
    if name not in _REGISTRY and not any(
        isinstance(k, KnobFamily) and name.startswith(k.prefix)
        for k in _REGISTRY.values()
    ):
        raise KeyError(f"cannot override undeclared knob {name!r}")
    with _OVERRIDES_LOCK:
        if value is None:
            _OVERRIDES.pop(name, None)
        else:
            _OVERRIDES[name] = str(value)


def clear_runtime_override(name: str) -> None:
    set_runtime_override(name, None)


def clear_runtime_overrides() -> None:
    """Drop every runtime override (tests / controller shutdown)."""
    with _OVERRIDES_LOCK:
        _OVERRIDES.clear()


def runtime_overrides() -> dict:
    """Snapshot of the active overrides ({name: raw_string})."""
    with _OVERRIDES_LOCK:
        return dict(_OVERRIDES)


class Knob:
    """One declared environment knob."""

    __slots__ = ("name", "type", "default", "doc", "fallback", "group")

    def __init__(self, name: str, type: type, default, doc: str,
                 fallback: str | None = None, group: str = "general"):
        if name in _REGISTRY:
            raise ValueError(f"knob {name} declared twice")
        self.name = name
        self.type = type
        self.default = default
        self.doc = doc
        self.fallback = fallback
        self.group = group
        _REGISTRY[name] = self

    def raw(self) -> str | None:
        """The raw string value — runtime override first, then the env,
        then the fallback var; None when unset (empty string counts as
        unset)."""
        val = _OVERRIDES.get(self.name)
        if val is None or val == "":
            val = os.environ.get(self.name)
        if (val is None or val == "") and self.fallback:
            val = os.environ.get(self.fallback)
        if val == "":
            val = None
        return val

    def is_set(self) -> bool:
        return self.raw() is not None

    def get(self, default=_UNSET):
        """Parsed value, or the (declared or overridden) default when unset.

        Raises ValueError naming the knob on an unparseable value — a typo'd
        knob must fail loudly at read time, not act as silently-default.
        """
        raw = self.raw()
        if raw is None:
            return self.default if default is _UNSET else default
        try:
            return self._parse(raw)
        except (TypeError, ValueError) as e:
            raise ValueError(
                f"{self.name}={raw!r} is not a valid {self.type.__name__}: {e}"
            ) from e

    def _parse(self, raw: str):
        if self.type is bool:
            return raw.strip().lower() not in _BOOL_FALSE
        if self.type is int:
            return int(raw, 0)
        if self.type is float:
            return float(raw)
        return raw

    def __repr__(self):
        return f"Knob({self.name}, {self.type.__name__}, default={self.default!r})"


class KnobFamily:
    """A dynamic family of knobs sharing a prefix (``TPURX_FT_<FIELD>``):
    individual members are per-config-field overrides that can't be
    enumerated statically, but the family itself is declared and documented
    here like any other knob."""

    __slots__ = ("prefix", "doc", "group")

    def __init__(self, prefix: str, doc: str, group: str = "general"):
        if prefix in _REGISTRY:
            raise ValueError(f"knob family {prefix} declared twice")
        self.prefix = prefix
        self.doc = doc
        self.group = group
        _REGISTRY[prefix] = self

    def raw(self, field: str) -> str | None:
        """Raw value of ``<prefix><FIELD>`` (field upper-cased), None if unset."""
        name = self.prefix + field.upper()
        val = _OVERRIDES.get(name)
        return os.environ.get(name) if val is None else val


def all_knobs():
    """Every declared Knob/KnobFamily, sorted by name."""
    return [_REGISTRY[k] for k in sorted(_REGISTRY)]


def lookup(name: str):
    return _REGISTRY.get(name)


# ---------------------------------------------------------------------------
# Knob catalog.  Grouped to match docs/configuration.md sections.
# ---------------------------------------------------------------------------

# -- job identity (set by the launcher, read everywhere) --------------------
RANK = Knob(
    "TPURX_RANK", int, 0, "Global rank of this worker.",
    fallback="RANK", group="identity")
LOCAL_RANK = Knob(
    "TPURX_LOCAL_RANK", int, 0, "Rank local to this host.",
    fallback="LOCAL_RANK", group="identity")
WORLD_SIZE = Knob(
    "TPURX_WORLD_SIZE", int, 1, "Total ranks in the job.",
    fallback="WORLD_SIZE", group="identity")
GROUP_RANK = Knob(
    "TPURX_GROUP_RANK", int, 0,
    "Node index within the job (one per agent/host).", group="identity")
NNODES = Knob(
    "TPURX_NNODES", int, 1, "Number of nodes (agents) in the job.",
    group="identity")
INFRA_RANK = Knob(
    "TPURX_INFRA_RANK", int, None,
    "Infrastructure-assigned rank used for log prefixes before the "
    "launcher assigns TPURX_RANK.", group="identity")
CYCLE = Knob(
    "TPURX_CYCLE", int, 0,
    "Restart-cycle counter, bumped by the launcher on every restart; "
    "namespaces store keys and checkpoint rounds.", group="identity")
REPO = Knob(
    "TPURX_REPO", str, None,
    "Absolute path to the repo checkout; set by test and example harnesses for "
    "their generated worker scripts.", group="identity")

# -- control-plane store ----------------------------------------------------
STORE_ADDR = Knob(
    "TPURX_STORE_ADDR", str, "127.0.0.1",
    "Host of the control-plane store (seed shard when sharded).",
    group="store")
STORE_PORT = Knob(
    "TPURX_STORE_PORT", int, 29500,
    "Port of the control-plane store seed.", group="store")
STORE_SHARDS = Knob(
    "TPURX_STORE_SHARDS", str, None,
    "Comma-separated host:port shard endpoints; set selects the sharded "
    "store client (consistent-hash routing, per-shard failover).",
    group="store")
STORE_ENDPOINTS = Knob(
    "TPURX_STORE_ENDPOINTS", str, None,
    "Comma-separated host:port shard endpoints, overriding the "
    "shard-map bootstrap read.", group="store")
STORE_SPARES = Knob(
    "TPURX_STORE_SPARES", str, None,
    "Comma-separated host:port spare store endpoints a dead shard can be "
    "promoted onto (CAS'd epoch bump on the shard map); also consulted by "
    "clients re-fetching the map when every mapped endpoint is down.",
    group="store")
NATIVE_STORE = Knob(
    "TPURX_NATIVE_STORE", bool, False,
    "Launcher hosts the native C++ store server instead of the asyncio "
    "one.", group="store")
TREE_FANOUT = Knob(
    "TPURX_TREE_FANOUT", int, 16,
    "Fan-out of the rank→host→job reduction tree used by every "
    "cross-rank gather round.", group="store")
TREE_PAYLOAD_CAP = Knob(
    "TPURX_TREE_PAYLOAD_CAP", int, 0,
    "Byte cap on the combined payload a tree-gather node publishes upward; "
    "over-cap payloads are trimmed (stride-sampled with a '_trimmed' "
    "marker) at every level when the caller opts into a trim function. "
    "0 = unbounded.", group="store")
STORE_POLL_S = Knob(
    "TPURX_STORE_POLL_S", float, 0.5,
    "Poll quantum of the store client's interruptible I/O core: no socket "
    "connect/send/recv sits in one C-level wait longer than this — every "
    "blocking op is a Python-level retry loop, so pending async raises "
    "(in-process restarts), monitor aborts and shutdown land between "
    "slices.", group="store")
STORE_TEST_COMPACT_CRASH = Knob(
    "TPURX_STORE_TEST_COMPACT_CRASH", int, None,
    "TEST-ONLY fault hook: crash the store journal compactor after N "
    "appends.", group="store")
STORE_TEST_BROWNOUT = Knob(
    "TPURX_STORE_TEST_BROWNOUT", bool, False,
    "TEST-ONLY fault mode: the store server accepts connections and reads "
    "requests but never answers (a wedged serving loop behind a live TCP "
    "listener); clients must escape via per-op deadlines and trip "
    "failover.", group="store")
JAX_COORDINATOR = Knob(
    "TPURX_JAX_COORDINATOR", str, None,
    "host:port for jax.distributed.initialize; default derives "
    "store host and port+1.", group="store")

# -- heartbeat / hang detection --------------------------------------------
RANK_MONITOR_SOCKET = Knob(
    "TPURX_RANK_MONITOR_SOCKET", str, None,
    "Unix socket path of this rank's monitor server (set by the "
    "launcher).", group="detection")
LAUNCHER_IPC_SOCKET = Knob(
    "TPURX_LAUNCHER_IPC_SOCKET", str, None,
    "Unix socket for worker→launcher section/heartbeat IPC.",
    group="detection")
OPRING_SHM = Knob(
    "TPURX_OPRING_SHM", str, None,
    "Name of the dispatched-op ring shm segment (set by the straggler "
    "detector, read by the monitor for at-abort fingerprints).",
    group="detection")
BEAT_PIN_CPU = Knob(
    "TPURX_BEAT_PIN_CPU", int, None,
    "CPU to pin the native beater thread to (-1 disables; default "
    "picks the last online CPU).", group="detection")
BEAT_RT_PRIO = Knob(
    "TPURX_BEAT_RT_PRIO", int, 1,
    "SCHED_FIFO priority requested for the native beater (EPERM falls "
    "back to normal scheduling).", group="detection")
FT_OVERRIDES = KnobFamily(
    "TPURX_FT_",
    "Per-field overrides of FaultToleranceConfig: TPURX_FT_<UPPER_FIELD> "
    "(e.g. TPURX_FT_RANK_HEARTBEAT_TIMEOUT=null disables that timeout). "
    "Highest-precedence config source.", group="detection")

# -- checkpointing ----------------------------------------------------------
CKPT_CHUNK_BYTES = Knob(
    "TPURX_CKPT_CHUNK_BYTES", int, 16 << 20,
    "Chunk size of the multi-threaded checkpoint drain/restore engines.",
    group="checkpoint")
CKPT_RESTORE_THREADS = Knob(
    "TPURX_CKPT_RESTORE_THREADS", int, 0,
    "Restore read-engine thread count (0 = same sizing as the write "
    "engine).", group="checkpoint")
CKPT_DIGEST = Knob(
    "TPURX_CKPT_DIGEST", bool, True,
    "Compute per-chunk crc32 spans + composed shard digests during the "
    "drain.", group="checkpoint")
CKPT_DIRECT_IO = Knob(
    "TPURX_CKPT_DIRECT_IO", bool, True,
    "Use O_DIRECT for checkpoint reads/writes (buffered fallback on "
    "EINVAL).", group="checkpoint")
CKPT_SCRUB_INTERVAL = Knob(
    "TPURX_CKPT_SCRUB_INTERVAL", float, None,
    "Idle-time integrity scrubber period in seconds (unset disables).",
    group="checkpoint")
CKPT_STAGER_NICE = Knob(
    "TPURX_CKPT_STAGER_NICE", int, 10,
    "nice() increment applied to the async-save stager thread.",
    group="checkpoint")
CKPT_WORKER_NICE = Knob(
    "TPURX_CKPT_WORKER_NICE", int, 10,
    "nice() increment applied to the checkpoint writer process.",
    group="checkpoint")
CKPT_WORKER_IONICE = Knob(
    "TPURX_CKPT_WORKER_IONICE", int, 3,
    "ionice class for the checkpoint writer process (3 = idle).",
    group="checkpoint")
PEER_ADDR = Knob(
    "TPURX_PEER_ADDR", str, None,
    "Override of the replication peer address map: "
    "'rank:host:port,rank:host:port'.", group="checkpoint")
CKPT_RESIDENT = Knob(
    "TPURX_CKPT_RESIDENT", bool, True,
    "Keep the last committed checkpoint generation memory-resident (the "
    "staging shm pool / replica blobs) as the warm restore source.",
    group="checkpoint")
CKPT_DELTA = Knob(
    "TPURX_CKPT_DELTA", bool, False,
    "Delta saves: skip draining chunks whose crc32 matches the previous "
    "committed index (requires digests; per-save delta= overrides; the "
    "index records per-chunk provenance so restores cover every byte).",
    group="checkpoint")
CKPT_DEVICE_DIGEST = Knob(
    "TPURX_CKPT_DEVICE_DIGEST", bool, False,
    "Compute per-chunk change fingerprints on-device before staging: delta "
    "saves skip the D2H transfer (not just the disk write) for shards whose "
    "fingerprints all match the committed baseline, and every transferred "
    "chunk's device verdict is cross-checked against the host crc32 "
    "(disagreement fails the save as a detected corruption).",
    group="checkpoint")
CKPT_STAGE_BUFFERS = Knob(
    "TPURX_CKPT_STAGE_BUFFERS", int, 2,
    "Device-side snapshot slots of the async-save ring (snapshot stage "
    "mode): with >=2, the next step's snapshot reuses a slot whose staging "
    "already drained (its memory released to the new copy) so compute "
    "overlaps the previous slice's D2H; 1 restores the single-copy "
    "behavior.",
    group="checkpoint")
CKPT_PEER_STREAMS = Knob(
    "TPURX_CKPT_PEER_STREAMS", int, 4,
    "Concurrent chunk streams of one peer-memory restore fetch.",
    group="checkpoint")
CKPT_PEER_MEM_TIMEOUT = Knob(
    "TPURX_CKPT_PEER_MEM_TIMEOUT", float, 10.0,
    "Deadline of the peer-memory restore rung before the ladder falls "
    "through to disk (0 disables the rung).", group="checkpoint")
CKPT_PEER_TIMEOUT = Knob(
    "TPURX_CKPT_PEER_TIMEOUT", float, 120.0,
    "Deadline of one peer-retrieval exchange round (election + transfer); "
    "the LocalCheckpointManager peer_timeout ctor arg overrides.",
    group="checkpoint")

# -- telemetry / logging ----------------------------------------------------
TELEMETRY = Knob(
    "TPURX_TELEMETRY", bool, True,
    "Global telemetry switch; 0 swaps every metric for a shared no-op.",
    group="telemetry")
METRICS_PORT = Knob(
    "TPURX_METRICS_PORT", int, None,
    "Base port of the per-rank OpenMetrics HTTP endpoint "
    "(port + local_rank; 0 = ephemeral; unset disables).",
    group="telemetry")
METRICS_TEXTFILE = Knob(
    "TPURX_METRICS_TEXTFILE", str, None,
    "Atomic textfile sink path template for OpenMetrics output "
    "(%r = rank, %h = host).", group="telemetry")
PROFILING_FILE = Knob(
    "TPURX_PROFILING_FILE", str, None,
    "JSONL profiling-event sink path (%r expanded to rank).",
    group="telemetry")
PROFILING_HISTORY = Knob(
    "TPURX_PROFILING_HISTORY", int, 4096,
    "Bounded in-memory profiling event history per process.",
    group="telemetry")
LOG_LEVEL = Knob(
    "TPURX_LOG_LEVEL", str, "INFO", "Root log level for tpurx loggers.",
    group="telemetry")
LOG_FILE = Knob(
    "TPURX_LOG_FILE", str, None,
    "Log file path template (%r expanded to rank, deferred to first "
    "record).", group="telemetry")
LOG_FUNNEL = Knob(
    "TPURX_LOG_FUNNEL", str, None,
    "Unix socket of the per-node log funnel root (set by the launcher "
    "for workers).", group="telemetry")
FLIGHT = Knob(
    "TPURX_FLIGHT", bool, True,
    "Fault-episode flight recorder; 0 swaps the ring append for a shared "
    "no-op (same discipline as TPURX_TELEMETRY).", group="telemetry")
FLIGHT_RING = Knob(
    "TPURX_FLIGHT_RING", int, 4096,
    "Flight-recorder ring capacity in events (rounded up to a power of "
    "two; oldest events overwritten).", group="telemetry")
FLIGHT_DIR = Knob(
    "TPURX_FLIGHT_DIR", str, None,
    "Directory for flight-recorder black-box dumps (default: the "
    "system temp dir).  Only where it is set does a process also dump "
    "its ring once when it ends (reason `exit`).", group="telemetry")
FLIGHT_DUMP_KEEP = Knob(
    "TPURX_FLIGHT_DUMP_KEEP", int, 32,
    "Dump files retained per process; older dumps this process wrote "
    "are unlinked.", group="telemetry")
EPISODE_KEEP = Knob(
    "TPURX_EPISODE_KEEP", int, 16,
    "Fault-episode summaries retained in the store; older episodes are "
    "GC'd at close.", group="telemetry")
CLOCK_CAL = Knob(
    "TPURX_CLOCK_CAL", bool, True,
    "Store-mediated per-host clock-offset calibration at wrapper "
    "startup (rank 0 serves the reference).", group="telemetry")
CLOCK_CAL_ROUNDS = Knob(
    "TPURX_CLOCK_CAL_ROUNDS", int, 8,
    "Ping-pong rounds per clock calibration; the minimum-RTT round's "
    "midpoint estimate wins.", group="telemetry")
CLOCK_TEST_SKEW_NS = Knob(
    "TPURX_CLOCK_TEST_SKEW_NS", int, 0,
    "TEST-ONLY: artificial offset added to this process's monotonic "
    "clock so alignment tests can prove offset recovery.",
    group="telemetry")

# -- health / fault injection ----------------------------------------------
NODE_HEALTH_ENDPOINT = Knob(
    "TPURX_NODE_HEALTH_ENDPOINT", str, None,
    "HTTP endpoint of the node health daemon probed by the health "
    "gate.", group="health")
INJECT_NODE_FAILURE = Knob(
    "TPURX_INJECT_NODE_FAILURE", str, None,
    "TEST-ONLY: fake a node-health failure spec in the health gate.",
    group="health")
FAULT = Knob(
    "TPURX_FAULT", str, None,
    "Soak-harness fault spec to inject in this worker (class[:arg]).",
    group="health")
FAULT_RANKS = Knob(
    "TPURX_FAULT_RANKS", str, None,
    "Comma-separated ranks the injected fault applies to (default all).",
    group="health")
FAULT_CYCLES = Knob(
    "TPURX_FAULT_CYCLES", str, None,
    "Comma-separated restart cycles the injected fault fires in.",
    group="health")
FAULT_CKPT_DIR = Knob(
    "TPURX_FAULT_CKPT_DIR", str, None,
    "Checkpoint directory targeted by corruption fault classes.",
    group="health")
SHRINK_MESH = Knob(
    "TPURX_SHRINK_MESH", bool, False,
    "Enable the opt-in ShrinkMeshStage rung in the abort ladder.",
    group="health")
SKIP_JAX_LANE_CHECK = Knob(
    "TPURX_SKIP_JAX_LANE_CHECK", bool, False,
    "Skip the jax-version compatibility probe of the straggler "
    "device lane.", group="health")
SANITIZE = Knob(
    "TPURX_SANITIZE", bool, False,
    "Opt-in runtime lock-order sanitizer: wraps threading.Lock/RLock, "
    "records the cross-thread acquisition DAG, and raises "
    "LockOrderViolation on a runtime lock-order cycle.", group="health")
SANITIZE_WITNESS_PATH = Knob(
    "TPURX_SANITIZE_WITNESS_PATH", str, None,
    "JSONL witness sink for the lock-order sanitizer (%r = rank, "
    "%p = pid); feed it back with 'tpurx-lint --witness <file>' to "
    "confirm or prune static TPURX011 cycles.", group="health")

# -- collectives ------------------------------------------------------------
COLL_DEADLINE_MS = Knob(
    "TPURX_COLL_DEADLINE_MS", float, 30000.0,
    "Default per-op deadline for wrapped resiliency-layer collectives "
    "(ResilientCollective); <=0 disables deadlining (inline fast path).",
    group="collectives")
COLL_RETRIES = Knob(
    "TPURX_COLL_RETRIES", int, 2,
    "Bounded retry budget of the collective degrade ladder's first rung "
    "(re-attempts of the primary lane after a CollectiveTimeout).",
    group="collectives")
COLL_DEGRADE = Knob(
    "TPURX_COLL_DEGRADE", str, "retry,relayout,shrink",
    "Ordered degrade-ladder composition for wrapped collectives: "
    "comma-separated rungs from {retry, relayout, shrink} (empty string "
    "= fail fast on the first CollectiveTimeout).", group="collectives")

# -- adaptive policy --------------------------------------------------------
POLICY = Knob(
    "TPURX_POLICY", bool, False,
    "Enable the adaptive resiliency policy engine: a closed-loop "
    "controller that retunes save cadence (Young/Daly), replication, "
    "delta saves, and restart/degrade rungs from measured fault rates.",
    group="policy")
POLICY_INTERVAL_S = Knob(
    "TPURX_POLICY_INTERVAL_S", float, 30.0,
    "Tick period of the policy control loop (estimator refresh + "
    "actuation).", group="policy")
POLICY_WINDOW_S = Knob(
    "TPURX_POLICY_WINDOW_S", float, 300.0,
    "Sliding window the estimator reads fault/interruption rates over.",
    group="policy")
POLICY_CADENCE_MIN_S = Knob(
    "TPURX_POLICY_CADENCE_MIN_S", float, 10.0,
    "Lower clamp of the policy-set checkpoint save interval.",
    group="policy")
POLICY_CADENCE_MAX_S = Knob(
    "TPURX_POLICY_CADENCE_MAX_S", float, 3600.0,
    "Upper clamp of the policy-set checkpoint save interval.",
    group="policy")
POLICY_HYSTERESIS_PCT = Knob(
    "TPURX_POLICY_HYSTERESIS_PCT", float, 20.0,
    "Minimum relative change (percent) between the current and proposed "
    "cadence before the actuator applies it — damping against estimator "
    "noise flapping the knob every tick.", group="policy")
POLICY_RISK_THRESHOLD = Knob(
    "TPURX_POLICY_RISK_THRESHOLD", float, 0.5,
    "Node failure-risk score (0-1) above which the controller raises "
    "replication and flips delta saves on ahead of the predicted "
    "failure.", group="policy")
EVAC = Knob(
    "TPURX_EVAC", bool, False,
    "Enable predict-and-evacuate: when a rank's fused risk score "
    "(straggler + health + kmsg + route bias) crosses the evacuation "
    "threshold, the controller emits a typed evacuate(rank) action that "
    "drives checkpoint-ahead, spare promotion, and a victim-scoped mesh "
    "shrink before the predicted hard fault.", group="policy")
EVAC_RISK_THRESHOLD = Knob(
    "TPURX_EVAC_RISK_THRESHOLD", float, 0.7,
    "Per-rank fused risk score (0-1) above which the controller "
    "evacuates the rank.  Must hold for two consecutive ticks (false-"
    "positive guard); deliberately above TPURX_POLICY_RISK_THRESHOLD so "
    "checkpoint-ahead hardening always precedes evacuation.",
    group="policy")
EVAC_HYSTERESIS_PCT = Knob(
    "TPURX_EVAC_HYSTERESIS_PCT", float, 25.0,
    "Relative margin (percent) below TPURX_EVAC_RISK_THRESHOLD a rank's "
    "risk must fall before the evacuation trigger re-arms — damping "
    "against a score oscillating around the threshold re-evacuating on "
    "every crossing.", group="policy")
EVAC_JOIN_TIMEOUT = Knob(
    "TPURX_EVAC_JOIN_TIMEOUT", float, 60.0,
    "Deadline (seconds) for the replacement rank's warm join: fetching "
    "the evacuated rank's shards chunk-granular from peer holders.  Past "
    "it the join falls back to the cold global-restore round.",
    group="policy")
CKPT_INTERVAL_S = Knob(
    "TPURX_CKPT_INTERVAL_S", float, None,
    "Target seconds between async checkpoint saves; SaveScheduler reads "
    "it per step, so policy runtime overrides retune cadence mid-run.",
    group="checkpoint")
LCKPT_REPLICATION = Knob(
    "TPURX_LCKPT_REPLICATION", int, None,
    "Override of the local-checkpoint replication factor, consulted per "
    "save (the CliqueReplication ctor value is the floor default).",
    group="checkpoint")

# -- attribution / LLM ------------------------------------------------------
LLM_BASE_URL = Knob(
    "TPURX_LLM_BASE_URL", str, "",
    "OpenAI-compatible endpoint for LLM-backed log attribution "
    "(empty disables).", group="attribution")
LLM_API_KEY = Knob(
    "TPURX_LLM_API_KEY", str, "", "API key for the attribution LLM.",
    group="attribution")
LLM_MODEL = Knob(
    "TPURX_LLM_MODEL", str, "default",
    "Model name for the attribution LLM.", group="attribution")
LLM_TIMEOUT_S = Knob(
    "TPURX_LLM_TIMEOUT_S", float, 30.0,
    "Per-request timeout for the attribution LLM.", group="attribution")

_GROUP_TITLES = {
    "identity": "Job identity",
    "store": "Control-plane store",
    "detection": "Heartbeat & hang detection",
    "checkpoint": "Checkpointing",
    "telemetry": "Telemetry & logging",
    "health": "Health & fault injection",
    "collectives": "Collectives",
    "policy": "Adaptive policy",
    "attribution": "Attribution / LLM",
    "general": "General",
}


def render_markdown() -> str:
    """docs/configuration.md content, generated from the declarations."""
    lines = [
        "# Configuration — TPURX_* environment knobs",
        "",
        "**Generated from `tpu_resiliency/utils/env.py` — do not edit by "
        "hand.**  Regenerate with `python -m tpu_resiliency.utils.env "
        "--write` after declaring a knob.",
        "",
        "Conventions: empty string == unset; booleans treat "
        "`0/false/no/off` as false and anything else set as true; every "
        "library read goes through the typed registry (lint rule TPURX010).",
        "",
    ]
    by_group: dict = {}
    for knob in all_knobs():
        by_group.setdefault(knob.group, []).append(knob)
    for group in _GROUP_TITLES:
        knobs = by_group.pop(group, [])
        if not knobs:
            continue
        lines += [f"## {_GROUP_TITLES[group]}", "",
                  "| Name | Type | Default | Description |",
                  "| --- | --- | --- | --- |"]
        for k in knobs:
            if isinstance(k, KnobFamily):
                lines.append(
                    f"| `{k.prefix}<FIELD>` | family | — | {k.doc} |")
            else:
                fb = f" (falls back to `{k.fallback}`)" if k.fallback else ""
                default = "unset" if k.default is None else f"`{k.default}`"
                lines.append(
                    f"| `{k.name}` | {k.type.__name__} | {default} | "
                    f"{k.doc}{fb} |")
        lines.append("")
    assert not by_group, f"groups missing a title: {sorted(by_group)}"
    return "\n".join(lines)


def force_cpu_env(env: dict) -> dict:
    """Keep a child python off the accelerator: ``JAX_PLATFORMS=cpu``.

    A chip belongs to one process at a time, so every helper that may import
    jax while a worker holds the chip (checkpoint writers, monitors, store
    shards, CPU benchmark arms) is started with this env.  Mutates and
    returns ``env``.
    """
    env["JAX_PLATFORMS"] = "cpu"
    return env


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m tpu_resiliency.utils.env",
        description="Regenerate docs/configuration.md from the knob registry.")
    default_doc = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "docs", "configuration.md")
    ap.add_argument("--write", nargs="?", const=default_doc, metavar="PATH",
                    help=f"write the generated catalog (default: {default_doc})")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 if the doc on disk is stale")
    args = ap.parse_args(argv)

    content = render_markdown()
    target = args.write or default_doc
    if args.check:
        try:
            with open(target) as f:
                on_disk = f.read()
        except OSError:
            on_disk = ""
        if on_disk != content:
            import sys
            sys.stderr.write(f"{target} is stale — regenerate with "
                             f"python -m tpu_resiliency.utils.env --write\n")
            return 1
        return 0
    with open(target, "w") as f:
        f.write(content)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
