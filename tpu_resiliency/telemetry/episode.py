"""Fault-episode identity and per-phase MTTR decomposition.

An *episode* is one fault's life: first detection → decision → abort →
rendezvous → restore → resume.  The episode id is minted **at first
detection** with a store ADD (so every rank that detects the same fault
converges on one id via a compare-set claim keyed by the restart
iteration), propagated through the restart pipeline, rendezvous records,
policy journal rows and checkpoint restore, and stamped onto every flight
and profiling event the participating processes emit — the join key that
turns per-process dumps into one causal story.

Phase accounting is transition-based: :meth:`Episode.phase` ends the
current phase and starts the named one, so the decomposed phases sum to
the episode's wall time by construction (:meth:`Episode.coverage_pct`
reads any uninstrumented gap).  At
:meth:`Episode.close` each phase lands in
``tpurx_episode_phase_ns{phase,fault_class}`` and the per-rank summary is
published to the store under ``episode/<id>/rank/<r>`` for ``smonsvc``'s
``GET /episodes``; episodes older than ``TPURX_EPISODE_KEEP`` are GC'd.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from ..utils import env
from ..utils.logging import get_logger
from . import flight, histogram
from .clock import mono_ns

log = get_logger("telemetry.episode")

PHASES = (
    "detect", "decide", "evacuate", "abort", "rendezvous", "restore", "resume"
)
# phases a REACTIVE episode (fault fired first) walks; "evacuate" only
# appears when the policy's predict-and-evacuate loop preempted the fault
REACTIVE_PHASES = tuple(p for p in PHASES if p != "evacuate")

_PHASE_NS = histogram(
    "tpurx_episode_phase_ns",
    "Per-fault-episode phase wall time, decomposing MTTR by fault class",
    labels=("phase", "fault_class"),
)

EV_BEGIN = flight.declare_event("episode.begin", "episode", "fault_class")
EV_PHASE = flight.declare_event("episode.phase", "episode", "phase")
EV_CLOSE = flight.declare_event(
    "episode.close", "episode", "fault_class", "wall_ns"
)

SEQ_KEY = "episode/seq"
CURRENT_KEY = "episode/current"

_lock = threading.Lock()
_current: Optional["Episode"] = None
_recent: List["Episode"] = []   # closed episodes, in-process (recent())
_RECENT_KEEP = 64
_local_seq = itertools.count(1)


class Episode:
    """One fault episode as seen by this process."""

    def __init__(
        self,
        episode_id: str,
        fault_class: str = "unknown",
        store=None,
        rank: Optional[int] = None,
    ):
        self.id = episode_id
        self.fault_class = fault_class
        self.store = store
        self.rank = env.RANK.get() if rank is None else rank
        self.t0_ns = mono_ns()
        self.closed_ns: Optional[int] = None
        self._marks: List[tuple] = [("detect", self.t0_ns)]
        flight.set_current_episode(self.id)
        flight.record(EV_BEGIN, self.id, fault_class)
        flight.record(EV_PHASE, self.id, "detect")

    def phase(self, name: str) -> None:
        """End the running phase, start ``name`` (idempotent per phase)."""
        if self.closed_ns is not None or self._marks[-1][0] == name:
            return
        self._marks.append((name, mono_ns()))
        flight.record(EV_PHASE, self.id, name)

    def current_phase(self) -> str:
        return self._marks[-1][0]

    def set_fault_class(self, fault_class: str) -> None:
        if fault_class:
            self.fault_class = fault_class

    @property
    def phases_ns(self) -> Dict[str, int]:
        """Per-phase wall time; the running phase extends to now."""
        end = self.closed_ns if self.closed_ns is not None else mono_ns()
        out: Dict[str, int] = {}
        for (name, start), (_next_name, nxt) in zip(
            self._marks, self._marks[1:] + [("", end)]
        ):
            out[name] = out.get(name, 0) + (nxt - start)
        return out

    @property
    def wall_ns(self) -> int:
        end = self.closed_ns if self.closed_ns is not None else mono_ns()
        return end - self.t0_ns

    def coverage_pct(self) -> float:
        """How much of the episode's wall time the decomposed phases
        cover — <100 means an uninstrumented gap."""
        wall = self.wall_ns
        if wall <= 0:
            return 100.0
        return 100.0 * sum(self.phases_ns.values()) / wall

    def close(self) -> Dict[str, int]:
        """End the episode: observe phase histograms, publish the per-rank
        summary, clear the process's current-episode tag."""
        global _current
        if self.closed_ns is not None:
            return self.phases_ns
        self.closed_ns = mono_ns()
        phases = self.phases_ns
        for name, dur in phases.items():
            _PHASE_NS.labels(name, self.fault_class).observe(dur)
        flight.record(EV_CLOSE, self.id, self.fault_class, self.wall_ns)
        with _lock:
            if _current is self:
                _current = None
            _recent.append(self)
            del _recent[:-_RECENT_KEEP]
        if flight.current_episode_id() == self.id:
            flight.set_current_episode("")
        if self.store is not None:
            try:
                self.store.set(
                    f"episode/{self.id}/rank/{self.rank}",
                    json.dumps(self.summary()),
                )
                if self.rank == 0:
                    self.store.set(CURRENT_KEY, b"")
                    _gc(self.store, self.id)
            except Exception:  # noqa: BLE001 - publication is best-effort
                log.debug("episode summary publish failed", exc_info=True)
        log.info(
            "episode %s closed: fault_class=%s wall=%.1fms phases=%s",
            self.id, self.fault_class, self.wall_ns / 1e6,
            {k: round(v / 1e6, 1) for k, v in phases.items()},
        )
        return phases

    def summary(self) -> Dict[str, Any]:
        return {
            "id": self.id,
            "rank": self.rank,
            "fault_class": self.fault_class,
            "pid": os.getpid(),
            "wall_ns": self.wall_ns,
            "phases_ns": self.phases_ns,
            "coverage_pct": round(self.coverage_pct(), 2),
            # wall stamp keys the fleet-wide "when" for humans; durations
            # above all come from the monotonic marks
            "t_close": time.time(),  # tpurx: disable=TPURX016 -- summary label, not a duration operand
        }


def _eid_num(episode_id: str) -> Optional[int]:
    if episode_id.startswith("ep") and episode_id[2:].isdigit():
        return int(episode_id[2:])
    return None


def _gc(store, episode_id: str) -> None:
    """Drop summaries of episodes older than the retention window."""
    n = _eid_num(episode_id)
    if n is None:
        return
    old = n - max(1, env.EPISODE_KEEP.get())
    if old <= 0:
        return
    try:
        for key in store.list_keys(f"episode/ep{old}/"):
            store.delete(key)
    except Exception:  # noqa: BLE001 - GC is best-effort
        log.debug("episode GC failed", exc_info=True)


def begin(
    store=None,
    claim=None,
    fault_class: str = "unknown",
    rank: Optional[int] = None,
) -> Episode:
    """Mint (or join) the episode for the fault just detected.

    ``claim``, when given, is a callable ``proposed_id -> winning_id``
    that arbitrates one id per fault across ranks (the in-process wrapper
    passes a compare-set on the iteration-scoped store key).  Without a
    store the id falls back to a process-local sequence — phases and
    flight tagging still work, only cross-process joining is off.
    """
    global _current
    with _lock:
        if _current is not None and _current.closed_ns is None:
            _current.set_fault_class(fault_class)
            return _current
    if store is not None:
        try:
            eid = f"ep{store.add(SEQ_KEY, 1)}"
            if claim is not None:
                eid = claim(eid)
            store.set(CURRENT_KEY, eid)
        except Exception:  # noqa: BLE001 - identity must not block recovery
            log.debug("episode mint via store failed", exc_info=True)
            eid = f"ep-local-{os.getpid()}-{next(_local_seq)}"
            store = None
    else:
        eid = f"ep-local-{os.getpid()}-{next(_local_seq)}"
    ep = Episode(eid, fault_class=fault_class, store=store, rank=rank)
    with _lock:
        _current = ep
    return ep


def current() -> Optional[Episode]:
    with _lock:
        return _current if (_current and _current.closed_ns is None) else None


def recent() -> List[Episode]:
    with _lock:
        return list(_recent)


def adopt(store) -> str:
    """Tag this process's flight/profiling events with the job's live
    episode id (sidecar processes: ckpt worker, monitor, smonsvc)."""
    try:
        raw = store.try_get(CURRENT_KEY)
    except Exception:  # noqa: BLE001 - adoption is best-effort
        return flight.current_episode_id()
    eid = (raw or b"").decode() if isinstance(raw, bytes) else (raw or "")
    if current() is None:
        flight.set_current_episode(eid)
    return eid


def current_or_store_id(store=None) -> str:
    """The episode id to stamp into journal/ledger rows: the process's
    live episode, else the job-wide current key when a store is at hand."""
    ep = current()
    if ep is not None:
        return ep.id
    eid = flight.current_episode_id()
    if eid or store is None:
        return eid
    try:
        raw = store.try_get(CURRENT_KEY)
    except Exception:  # noqa: BLE001 - stamping is best-effort
        return ""
    return (raw or b"").decode() if isinstance(raw, bytes) else (raw or "")


# -- store-side reading (smonsvc GET /episodes) ------------------------------


def read_episodes(store, n: int = 10) -> List[Dict[str, Any]]:
    """Last-``n`` episode summaries from the store, newest first: phase
    breakdown (max across ranks per phase), implicated ranks and the
    attribution verdict when one was published."""
    try:
        raw = store.try_get(SEQ_KEY)
        latest = int(raw) if raw else 0
    except Exception:  # noqa: BLE001 - a broken store reads as no episodes
        return []
    out: List[Dict[str, Any]] = []
    eid_n = latest
    while eid_n > 0 and len(out) < n:
        eid = f"ep{eid_n}"
        eid_n -= 1
        try:
            keys = store.list_keys(f"episode/{eid}/")
        except Exception:  # noqa: BLE001
            break
        ranks: Dict[int, Dict[str, Any]] = {}
        verdict = None
        for key in keys:
            k = key.decode() if isinstance(key, bytes) else key
            raw = store.try_get(k)
            if not raw:
                continue
            try:
                payload = json.loads(raw)
            except ValueError:
                continue
            if k.endswith("/verdict"):
                verdict = payload
            elif "/rank/" in k:
                try:
                    ranks[int(k.rsplit("/", 1)[1])] = payload
                except (ValueError, IndexError):
                    continue
        if not ranks and verdict is None:
            continue
        phase_ns: Dict[str, int] = {}
        for summary in ranks.values():
            for name, dur in (summary.get("phases_ns") or {}).items():
                phase_ns[name] = max(phase_ns.get(name, 0), int(dur))
        fault_classes = sorted(
            {s.get("fault_class", "unknown") for s in ranks.values()}
        )
        out.append({
            "id": eid,
            "fault_class": (fault_classes or ["unknown"])[0],
            "ranks": {str(r): ranks[r] for r in sorted(ranks)},
            "phase_ns": phase_ns,
            "wall_ns": max(
                (int(s.get("wall_ns", 0)) for s in ranks.values()), default=0
            ),
            "implicated_ranks": (verdict or {}).get("culprit_ranks", []),
            "verdict": verdict,
        })
    return out
