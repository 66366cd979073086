"""Sharded control-plane store: consistent-hash routing + per-shard failover.

A single-process KV store is an O(N) hotspot and a single point of failure
for every coordination path (rendezvous counts, quorum rounds, telemetry
gathers, replication verdicts) — exactly the component Guard (PAPERS.md)
says must scale with the fleet.  This module spreads the keyspace over K
independent :class:`~tpu_resiliency.store.server.StoreServer` shards:

- :class:`ShardMap` — a consistent-hash ring (crc32 space, ``vnodes``
  virtual points per shard) mapping every key to one shard.  Adding or
  removing a shard moves ~1/K of the keyspace, not all of it.
- :class:`ShardedStoreClient` — the same primitive surface as
  :class:`~tpu_resiliency.store.client.StoreClient`, routing each op to the
  owning shard.  Per-key semantics (atomic ADD / COMPARE_SET, blocking
  GET/WAIT) are preserved because each key lives on exactly one
  single-threaded shard; multi-key ops (``wait``, ``check``, ``multi_*``,
  ``list_keys``, ``num_keys``) split per shard and recombine.
- **Failover contract**: every shard keeps its own journal, and a dead
  shard's replacement is journal-replayed on the same endpoint.  Idempotent
  ops ride the base client's reconnect; on top of that the sharded client
  retries a whole op episode on the ``store_shard_failover`` policy while a
  replacement comes up, and recovers interrupted COMPARE_SETs by value
  inspection (``store_cas_failover`` site) — callers see one slow round
  trip, never an error, for any fault the journal covers.
- **Bootstrap**: the shard map is published on the seed shard under
  :data:`SHARD_MAP_KEY`; a client that only knows the rendezvous seed
  endpoint (``TPURX_STORE_ADDR/PORT``) calls
  :meth:`ShardedStoreClient.from_bootstrap`.  Launchers set
  ``TPURX_STORE_SHARDS=h1:p1,h2:p2,...`` to skip the extra hop.

Server side, :class:`ShardServerGroup` hosts K asyncio shards in one
process (tests, single-host jobs) and :func:`spawn_shard_subprocess` spawns
one shard as a separate kill-able process (soak fault injection,
production one-process-per-core layouts).
"""

from __future__ import annotations

import bisect
import json
import os
import socket
import subprocess
import sys
import threading
import time
import zlib
from typing import Callable, List, Optional, Sequence, Tuple

from ..telemetry import counter, gauge
from ..utils import env
from ..utils.logging import get_logger
from ..utils.retry import Retrier, RetryExhausted, RetryPolicy

# fixed-cadence subprocess-start poll (local child; no jitter needed)
_SPAWN_POLL = RetryPolicy(max_attempts=None, base_delay=0.05, max_delay=0.05,
                          min_delay_fraction=1.0)
from .client import (
    _DEFAULT_TIMEOUT,
    StoreClient,
    StoreError,
    StoreTimeout,
    _interruptible_sleep,
    _poll_quantum,
)

log = get_logger("store.sharding")

SHARD_MAP_KEY = "store/shard_map"

# episode-level failover budget while a journal-replayed replacement shard
# comes up (the base client's own reconnect budget is ~seconds; this rides
# above it and covers a scheduler-speed respawn)
FAILOVER_POLICY = RetryPolicy(
    max_attempts=None, base_delay=0.5, max_delay=5.0, deadline=60.0
)

_SHARD_OPS = counter(
    "tpurx_store_shard_ops_total",
    "KV store ops routed per shard by the sharded client",
    labels=("shard",),
)
_SHARD_FAILOVERS = counter(
    "tpurx_store_shard_failovers_total",
    "Op episodes that had to ride out a shard death (reconnect + retry)",
    labels=("shard",),
)
_SHARD_COUNT = gauge(
    "tpurx_store_shard_count", "Shards in this client's shard map"
)


def _parse_endpoints(endpoints) -> List[Tuple[str, int]]:
    out = []
    for e in endpoints:
        if isinstance(e, str):
            host, _, port = e.rpartition(":")
            out.append((host, int(port)))
        else:
            host, port = e
            out.append((host, int(port)))
    if not out:
        raise ValueError("need at least one shard endpoint")
    return out


def affinity_token(key: bytes) -> Optional[bytes]:
    """The affinity-group token for ``key``, or None for per-key routing.

    Keys of one protocol round hash as a unit so a round's multi-key
    one-RTT ops (APPEND_CHECK, ADD_SET) are guaranteed single-shard:

    - ``rdzv/{n}/...`` (numeric round segment) -> ``rdzv/{n}``
    - ``barrier/{name}/...`` -> ``barrier/{name}``

    Fixed rendezvous pointers (``rdzv/active_round`` etc.) have a
    non-numeric second segment and keep per-key routing, as does every
    other keyspace — affinity narrows distribution only where a round's
    keys must be co-located.
    """
    parts = key.split(b"/", 2)
    if len(parts) < 3:
        return None
    if parts[0] == b"rdzv" and parts[1].isdigit():
        return b"rdzv/" + parts[1]
    if parts[0] == b"barrier":
        return b"barrier/" + parts[1]
    return None


class ShardMap:
    """Consistent-hash ring over shard endpoints (crc32 space).

    Hashing must be stable across processes and Python versions (builtin
    ``hash`` is salted), so both ring points and key lookups use crc32.
    Ring points are keyed by shard INDEX, not endpoint: a shard's identity
    is its position (which is also what names its journal, ``*.shard<i>``),
    so a replacement coming up on a different host:port — a restarted
    control plane re-binding ephemeral ports, or a spare promoted by
    :func:`promote_spare` — keeps the exact same key→shard routing the
    journals were written under.

    ``epoch`` versions the index→endpoint assignment: every spare
    promotion bumps it (under CAS on the published map), and clients
    inside a failover episode adopt any same-size map with a greater
    epoch.  ``spares`` lists endpoints a dead shard may be promoted onto.
    """

    def __init__(self, endpoints, vnodes: int = 64, epoch: int = 0,
                 spares: Sequence = ()):
        self.endpoints = _parse_endpoints(endpoints)
        self.vnodes = vnodes
        self.epoch = int(epoch)
        self.spares = _parse_endpoints(spares) if spares else []
        points: List[Tuple[int, int]] = []
        for idx in range(len(self.endpoints)):
            for v in range(vnodes):
                h = zlib.crc32(f"shard{idx}#{v}".encode())
                points.append((h, idx))
        points.sort()
        self._hashes = [h for h, _ in points]
        self._owners = [i for _, i in points]

    def __len__(self) -> int:
        return len(self.endpoints)

    def with_promoted(self, dead_idx: int, spare_endpoint) -> "ShardMap":
        """A new map with ``spare_endpoint`` serving shard ``dead_idx`` and
        the epoch bumped.  Key→index routing is untouched (the ring is keyed
        by index); the spare is consumed from ``spares`` if listed there."""
        (spare,) = _parse_endpoints([spare_endpoint])
        endpoints = [f"{h}:{p}" for h, p in self.endpoints]
        endpoints[dead_idx] = f"{spare[0]}:{spare[1]}"
        spares = [f"{h}:{p}" for h, p in self.spares if (h, p) != spare]
        return ShardMap(endpoints, vnodes=self.vnodes,
                        epoch=self.epoch + 1, spares=spares)

    def shard_for(self, key: bytes) -> int:
        """Owning shard index for ``key`` (first ring point clockwise)."""
        if len(self.endpoints) == 1:
            return 0
        h = zlib.crc32(key)
        i = bisect.bisect_right(self._hashes, h)
        if i == len(self._hashes):
            i = 0
        return self._owners[i]

    def to_json(self) -> str:
        out = {
            "endpoints": [f"{h}:{p}" for h, p in self.endpoints],
            "vnodes": self.vnodes,
            "epoch": self.epoch,
        }
        if self.spares:
            out["spares"] = [f"{h}:{p}" for h, p in self.spares]
        return json.dumps(out)

    @classmethod
    def from_json(cls, raw) -> "ShardMap":
        if isinstance(raw, bytes):
            raw = raw.decode()
        d = json.loads(raw)
        return cls(
            d["endpoints"],
            vnodes=int(d.get("vnodes", 64)),
            epoch=int(d.get("epoch", 0)),  # pre-epoch maps: epoch 0
            spares=d.get("spares", ()),
        )


def publish_shard_map(seed_client, shard_map: ShardMap) -> None:
    """Publish the map on the seed shard so bootstrap-only clients (that
    know nothing but the rendezvous endpoint) can discover the fleet."""
    seed_client.set(SHARD_MAP_KEY, shard_map.to_json())


def promote_spare(map_client, dead_idx: int, spare_endpoint=None,
                  timeout: float = 30.0) -> ShardMap:
    """Re-point shard ``dead_idx`` to a spare endpoint via a CAS'd epoch
    bump on the published map (``map_client`` talks to whichever server
    holds :data:`SHARD_MAP_KEY` — the seed, or seed's own journal-restored
    replacement when the seed is the dead shard).

    ``spare_endpoint`` defaults to the map's first listed spare.  Safe under
    concurrent promoters: the CAS loser re-reads, and if the winner already
    re-pointed the same shard, adopts the winner's map instead of promoting
    twice.  Returns the map now in force.
    """
    deadline = time.monotonic() + timeout
    while True:
        raw = map_client.get(SHARD_MAP_KEY, timeout=timeout)
        current = ShardMap.from_json(raw)
        spare = spare_endpoint
        if spare is None:
            if not current.spares:
                raise StoreError(
                    f"promote shard {dead_idx}: no spare endpoints in map"
                )
            spare = current.spares[0]
        promoted = current.with_promoted(dead_idx, spare)
        applied, after = map_client.compare_set_ex(
            SHARD_MAP_KEY, raw, promoted.to_json()
        )
        if applied:
            log.warning(
                "promoted spare %s to shard %d (map epoch %d)",
                spare, dead_idx, promoted.epoch,
            )
            return promoted
        winner = ShardMap.from_json(after)
        if (winner.epoch > current.epoch
                and winner.endpoints[dead_idx] != current.endpoints[dead_idx]):
            return winner  # a concurrent promoter already replaced it
        if time.monotonic() >= deadline:
            raise StoreError(
                f"promote shard {dead_idx}: lost the map CAS past deadline"
            )
        # unrelated concurrent map change: retry against the new state


class ShardedStoreClient:
    """Client over K store shards with consistent-hash key routing.

    Duck-typed to :class:`StoreClient`'s public surface; every caller
    (PrefixStore, barriers, rendezvous, quorum, verdict rounds) works
    unchanged.  Values ride to whichever single-threaded shard owns the key,
    so per-key atomicity (ADD, COMPARE_SET) and blocking waits keep their
    exact single-store semantics.
    """

    def __init__(
        self,
        endpoints,
        timeout: float = _DEFAULT_TIMEOUT,
        connect_timeout: float = 60.0,
        vnodes: int = 64,
        failover_policy: RetryPolicy = FAILOVER_POLICY,
        epoch: int = 0,
        spares: Sequence = (),
    ):
        self.map = ShardMap(endpoints, vnodes=vnodes, epoch=epoch,
                            spares=spares)
        self.endpoints = self.map.endpoints
        self.timeout = timeout
        self._connect_timeout = connect_timeout
        self._failover_policy = failover_policy
        self._clients: List[Optional[StoreClient]] = [
            StoreClient(h, p, timeout=timeout,
                        connect_timeout=connect_timeout)
            for h, p in self.endpoints
        ]
        self._shard_ops = [
            _SHARD_OPS.labels(str(i)) for i in range(len(self.endpoints))
        ]
        _SHARD_COUNT.set(len(self.endpoints))

    @classmethod
    def from_bootstrap(
        cls, host: str, port: int, timeout: float = _DEFAULT_TIMEOUT, **kwargs
    ) -> "ShardedStoreClient":
        """Discover the shard fleet from the seed endpoint: read the
        published :data:`SHARD_MAP_KEY` (blocking — the launcher publishes
        it during rendezvous bootstrap) and connect to every shard."""
        seed = StoreClient(host, port, timeout=timeout)
        try:
            raw = seed.get(SHARD_MAP_KEY, timeout=timeout)
        finally:
            seed.close()
        m = ShardMap.from_json(raw)
        return cls(m.endpoints, timeout=timeout, vnodes=m.vnodes,
                   epoch=m.epoch, spares=m.spares, **kwargs)

    # -- plumbing ----------------------------------------------------------

    def _shard_idx(self, key) -> int:
        k = key.encode() if isinstance(key, str) else bytes(key)
        tok = affinity_token(k)
        if tok is not None:
            k = tok
        return self.map.shard_for(k)

    def _client(self, idx: int) -> StoreClient:
        c = self._clients[idx]
        if c is None:
            host, port = self.endpoints[idx]
            c = StoreClient(host, port, timeout=self.timeout,
                            connect_timeout=self._connect_timeout)
            self._clients[idx] = c
        return c

    def _reconnect(self, idx: int) -> None:
        c, self._clients[idx] = self._clients[idx], None
        if c is not None:
            try:
                c.close()
            except OSError:
                pass

    def _fetch_map_raw(self, exclude: int) -> Optional[bytes]:
        """Best-effort read of the published shard map from any reachable
        server: live endpoints first (seed ahead — it holds the map), then
        the map's own spares, then ``TPURX_STORE_SPARES`` (covers the seed
        itself dying: its journal-restored spare holds the map key)."""
        candidates = [ep for i, ep in enumerate(self.endpoints)
                      if i != exclude]
        candidates += list(self.map.spares)
        raw_spares = env.STORE_SPARES.get()
        if raw_spares:
            candidates += _parse_endpoints(
                [e.strip() for e in raw_spares.split(",") if e.strip()]
            )
        seen = set()
        for host, port in candidates:
            if (host, port) in seen:
                continue
            seen.add((host, port))
            try:
                probe = StoreClient(host, port, timeout=5.0,
                                    connect_timeout=2.0, retries=0)
            except StoreError:
                continue
            try:
                raw = probe.try_get(SHARD_MAP_KEY)
            except (StoreError, StoreTimeout):
                continue
            finally:
                probe.close()
            if raw:
                return raw
        return None

    def _adopt_map(self, m: ShardMap) -> None:
        for i, (old, new) in enumerate(zip(self.endpoints, m.endpoints)):
            if old != new:
                log.warning(
                    "shard %d re-pointed %s:%d -> %s:%d (map epoch %d)",
                    i, old[0], old[1], new[0], new[1], m.epoch,
                )
                self._reconnect(i)
        self.map = m
        self.endpoints = m.endpoints

    def _maybe_adopt_promoted(self, idx: int) -> bool:
        """Inside shard ``idx``'s failover episode: look for an epoch-bumped
        map (a spare was promoted) and re-point re-indexed endpoints.  The
        ring is keyed by index, so adoption never moves keys — only where
        index ``idx`` connects."""
        raw = self._fetch_map_raw(exclude=idx)
        if raw is None:
            return False
        try:
            m = ShardMap.from_json(raw)
        except (ValueError, KeyError):
            return False
        if m.epoch <= self.map.epoch or len(m) != len(self.map):
            return False
        self._adopt_map(m)
        return True

    def _routed(self, idx: int, fn: Callable[[StoreClient], object]):
        """Run ``fn`` against shard ``idx``, riding out a shard death.

        The base client already retries transport-level failures of
        idempotent ops; what lands here as :class:`StoreError` is a shard
        that stayed dead past that budget.  The failover episode reconnects
        and re-runs under ``store_shard_failover`` until a replacement
        accepts — journal-replayed on the same endpoint, or an epoch-bumped
        spare discovered via the published map — or the policy deadline
        expires.  ``fn`` must be safe to re-run (idempotent op, or recovery
        logic like the CAS path).
        """
        self._shard_ops[idx].inc()
        retrier: Optional[Retrier] = None
        while True:
            try:
                return fn(self._client(idx))
            except StoreTimeout:
                raise  # caller's budget semantics, not a shard death
            except StoreError as exc:
                if retrier is None:
                    retrier = Retrier(
                        "store_shard_failover", self._failover_policy,
                        sleep=_interruptible_sleep,
                    )
                    _SHARD_FAILOVERS.labels(str(idx)).inc()
                host, port = self.endpoints[idx]
                log.warning(
                    "shard %d (%s:%d) unavailable (%s); waiting for its "
                    "replacement", idx, host, port, exc,
                )
                try:
                    retrier.backoff(exc)
                except RetryExhausted as give_up:
                    raise StoreError(
                        f"shard {idx} ({host}:{port}) did not come back: "
                        f"{give_up.last_exc}"
                    ) from give_up
                self._reconnect(idx)
                self._maybe_adopt_promoted(idx)

    def _by_shard(self, keys: Sequence) -> dict:
        """{shard_idx: [(position, key), ...]} preserving caller order."""
        groups: dict = {}
        for pos, key in enumerate(keys):
            groups.setdefault(self._shard_idx(key), []).append((pos, key))
        return groups

    # -- public API (mirrors StoreClient) ----------------------------------

    def clone(self) -> "ShardedStoreClient":
        return ShardedStoreClient(
            [f"{h}:{p}" for h, p in self.endpoints],
            timeout=self.timeout,
            vnodes=self.map.vnodes,
            failover_policy=self._failover_policy,
            epoch=self.map.epoch,
            spares=[f"{h}:{p}" for h, p in self.map.spares],
        )

    def close(self) -> None:
        for i, c in enumerate(self._clients):
            if c is not None:
                c.close()
                self._clients[i] = None

    def ping(self) -> bool:
        return all(
            self._routed(i, lambda c: c.ping())
            for i in range(len(self.endpoints))
        )

    def set(self, key, value) -> None:
        return self._routed(self._shard_idx(key), lambda c: c.set(key, value))

    def get(self, key, timeout: Optional[float] = None) -> bytes:
        t = self.timeout if timeout is None else timeout
        deadline = time.monotonic() + t
        idx = self._shard_idx(key)

        def attempt(c: StoreClient) -> bytes:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise StoreTimeout(f"get({key}) timed out after {t}s")
            return c.get(key, timeout=remaining)

        return self._routed(idx, attempt)

    def try_get(self, key) -> Optional[bytes]:
        return self._routed(self._shard_idx(key), lambda c: c.try_get(key))

    def add(self, key, amount: int = 1) -> int:
        # at-most-once like the base client: ADD cannot be blind-resent (a
        # double-applied arrival is a protocol corruption, not a retry)
        return self._shard_ops_inc_and_call(
            self._shard_idx(key), lambda c: c.add(key, amount)
        )

    def append(self, key, value) -> int:
        return self._shard_ops_inc_and_call(
            self._shard_idx(key), lambda c: c.append(key, value)
        )

    def _shard_ops_inc_and_call(self, idx: int, fn):
        self._shard_ops[idx].inc()
        return fn(self._client(idx))

    def compare_set(self, key, expected, desired) -> bytes:
        return self.compare_set_ex(key, expected, desired)[1]

    def compare_set_ex(self, key, expected, desired) -> Tuple[bool, bytes]:
        """CAS with failover recovery.

        A connection lost after the request left may or may not have applied
        the swap.  The journal-replayed replacement holds the truth: re-read
        the key — if it now holds ``desired``, the first send won (control-
        plane CAS values are round-fenced, so observing ``desired`` means
        OUR swap applied); otherwise re-issue the CAS.  Counted under the
        ``store_cas_failover`` retry site.
        """
        idx = self._shard_idx(key)
        self._shard_ops[idx].inc()
        retrier: Optional[Retrier] = None
        while True:
            try:
                return self._client(idx).compare_set_ex(key, expected, desired)
            except StoreTimeout:
                raise
            except StoreError as exc:
                if retrier is None:
                    retrier = Retrier(
                        "store_cas_failover", self._failover_policy,
                        sleep=_interruptible_sleep,
                    )
                    _SHARD_FAILOVERS.labels(str(idx)).inc()
                try:
                    retrier.backoff(exc)
                except RetryExhausted as give_up:
                    raise StoreError(
                        f"compare_set({key}): shard {idx} did not come "
                        f"back: {give_up.last_exc}"
                    ) from give_up
                self._reconnect(idx)
                self._maybe_adopt_promoted(idx)
                try:
                    current = self._client(idx).try_get(key)
                except (StoreError, StoreTimeout):
                    continue  # replacement not up yet: next backoff
                desired_b = StoreClient._v(desired)
                if current == desired_b:
                    return True, desired_b  # the interrupted send applied
                # not applied: loop re-issues the CAS against live state

    def wait(self, keys: Sequence, timeout: Optional[float] = None) -> None:
        """Block until every key exists.  Per-shard groups run CONCURRENTLY
        (one thread per extra shard): the overall fence latency is the MAX
        of the shard fences, where the historical sequential loop paid the
        SUM — at K shards a near-deadline straggler on each made the fence
        K times slower than the slowest shard."""
        t = self.timeout if timeout is None else timeout
        deadline = time.monotonic() + t
        groups = list(self._by_shard(keys).items())

        # Set when the CALLER abandons the fan-out (async raise landing in
        # the sliced join below).  Workers check it between park slices and
        # exit quietly instead of riding out the full wait budget — an
        # abandoned worker otherwise keeps holding its shard client's lock
        # and, once close() breaks its socket, thrashes store_shard_failover
        # episodes against a client nobody is using anymore.
        abandoned = threading.Event()

        def wait_shard(idx: int, group_keys: List) -> None:
            def attempt(c: StoreClient, _keys=group_keys) -> None:
                while not abandoned.is_set():
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise StoreTimeout(
                            f"wait({list(keys)}) timed out after {t}s"
                        )
                    try:
                        # one slice per call so the abandon flag is seen
                        # within a bounded park, not after `remaining`
                        c.wait(_keys, timeout=min(
                            remaining, StoreClient.BLOCKING_SLICE_S))
                        return
                    except StoreTimeout:
                        if deadline - time.monotonic() <= 0:
                            raise StoreTimeout(
                                f"wait({list(keys)}) timed out after {t}s"
                            )

            if not abandoned.is_set():
                self._routed(idx, attempt)

        if len(groups) == 1:  # common case: no thread overhead
            idx, group = groups[0]
            return wait_shard(idx, [k for _pos, k in group])
        errors: List[Optional[BaseException]] = [None] * len(groups)

        def run(slot: int, idx: int, group_keys: List) -> None:
            try:
                wait_shard(idx, group_keys)
            except BaseException as exc:  # re-raised on the caller thread
                errors[slot] = exc

        threads = [
            threading.Thread(
                target=run, args=(slot, idx, [k for _pos, k in group]),
                name=f"shard-wait-{idx}", daemon=True,
            )
            for slot, (idx, group) in enumerate(groups)
        ]
        for th in threads:
            th.start()
        # bound each join past the wait deadline by the failover episode's
        # own deadline: a shard mid-failover legitimately outlives the wait
        # budget, but a thread alive past BOTH is wedged — raise rather
        # than park forever
        join_deadline = deadline + self._failover_policy.deadline + 5.0
        try:
            for th in threads:
                # sliced join: one th.join(65.0) is a single C-level wait an
                # async raise (restart/abort) could never land in — park at
                # most one poll quantum per call so interrupts land between
                # slices
                while th.is_alive():
                    remaining = join_deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    th.join(timeout=min(_poll_quantum(), remaining))
                if th.is_alive():
                    raise StoreTimeout(
                        f"wait({list(keys)}): {th.name} still blocked "
                        f"{self._failover_policy.deadline + 5.0:.0f}s past "
                        f"the {t}s deadline"
                    )
        except BaseException:
            abandoned.set()  # workers exit at their next slice boundary
            raise
        # surface a hard shard error over a plain timeout: the timeout may
        # BE the dead shard, and the error names it
        for exc in errors:
            if exc is not None and not isinstance(exc, StoreTimeout):
                raise exc
        for exc in errors:
            if exc is not None:
                raise exc

    def check(self, keys: Sequence) -> bool:
        return all(
            self._routed(idx, lambda c, _k=[k for _p, k in g]: c.check(_k))
            for idx, g in self._by_shard(keys).items()
        )

    def delete(self, key) -> bool:
        return self._routed(self._shard_idx(key), lambda c: c.delete(key))

    def num_keys(self) -> int:
        return sum(
            self._routed(i, lambda c: c.num_keys())
            for i in range(len(self.endpoints))
        )

    def list_keys(self, prefix="") -> List[bytes]:
        out: List[bytes] = []
        for i in range(len(self.endpoints)):
            out.extend(self._routed(i, lambda c: c.list_keys(prefix)))
        return out

    def multi_set(self, items: dict) -> None:
        for idx, group in self._by_shard(list(items)).items():
            sub = {k: items[k] for _pos, k in group}
            self._routed(idx, lambda c, _s=sub: c.multi_set(_s))

    def multi_get(self, keys: Sequence) -> List[Optional[bytes]]:
        out: List[Optional[bytes]] = [None] * len(keys)
        for idx, group in self._by_shard(keys).items():
            vals = self._routed(
                idx, lambda c, _k=[k for _p, k in group]: c.multi_get(_k)
            )
            for (pos, _key), val in zip(group, vals):
                out[pos] = val
        return out

    # -- one-RTT protocol ops ---------------------------------------------
    # Multi-key atomic ops execute on ONE single-threaded shard; the keys'
    # co-location is ASSERTED here (affinity routing makes it hold — a
    # violation means the caller's keys fall outside an affinity group).

    def _colocated(self, op: str, key_a, key_b) -> int:
        i, j = self._shard_idx(key_a), self._shard_idx(key_b)
        if i != j:
            raise StoreError(
                f"{op}({key_a!r}, {key_b!r}): keys land on shards {i}/{j}; "
                f"one-RTT ops need both on one shard — route the round's "
                f"keys through an affinity group (affinity_token prefix)"
            )
        return i

    def append_check(
        self, key, value, done_key, done_value,
        required: int = 0, tokens: Sequence = (),
    ) -> Tuple[int, bool]:
        idx = self._colocated("append_check", key, done_key)
        # at-most-once like add/append: a resend would double-append
        return self._shard_ops_inc_and_call(
            idx,
            lambda c: c.append_check(
                key, value, done_key, done_value, required, tokens
            ),
        )

    def add_set(self, add_key, amount: int, set_key, set_value) -> int:
        idx = self._colocated("add_set", add_key, set_key)
        return self._shard_ops_inc_and_call(
            idx, lambda c: c.add_set(add_key, amount, set_key, set_value)
        )

    def wait_ge(self, key, threshold: int,
                timeout: Optional[float] = None) -> int:
        t = self.timeout if timeout is None else timeout
        deadline = time.monotonic() + t
        idx = self._shard_idx(key)

        def attempt(c: StoreClient) -> int:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise StoreTimeout(
                    f"wait_ge({key}, {threshold}) timed out after {t}s"
                )
            return c.wait_ge(key, threshold, timeout=remaining)

        return self._routed(idx, attempt)

    def affinity(self, prefix) -> "AffinityGroup":
        """A handle whose ops are guaranteed single-shard for every key
        under ``prefix`` (which should be an :func:`affinity_token` value,
        e.g. ``rdzv/7`` or ``barrier/restart``)."""
        return AffinityGroup(self, prefix)


class AffinityGroup:
    """Single-shard view over one protocol round's keys.

    Every op verifies its keys (a) carry the group's prefix and (b) route
    to the group's home shard — asserted per call, not assumed, so a
    mis-grouped key (one outside the round) fails
    loudly instead of splitting a one-RTT op across shards.  Delegates to
    the owning :class:`ShardedStoreClient`, so failover episodes and
    epoch adoption apply unchanged.
    """

    def __init__(self, base: ShardedStoreClient, prefix):
        self._base = base
        self._prefix = (
            prefix.decode() if isinstance(prefix, bytes) else str(prefix)
        ).rstrip("/")

    @property
    def prefix(self) -> str:
        return self._prefix

    @property
    def shard(self) -> int:
        return self._base._shard_idx(self._prefix)

    def _chk(self, *keys) -> None:
        home = self._base._shard_idx(self._prefix)
        for key in keys:
            k = key.decode() if isinstance(key, bytes) else str(key)
            if k != self._prefix and not k.startswith(self._prefix + "/"):
                raise StoreError(
                    f"key {k!r} is outside affinity group {self._prefix!r}"
                )
            idx = self._base._shard_idx(k)
            if idx != home:
                raise StoreError(
                    f"affinity violated: key {k!r} routes to shard {idx}, "
                    f"group {self._prefix!r} lives on shard {home}"
                )

    def set(self, key, value) -> None:
        self._chk(key)
        return self._base.set(key, value)

    def get(self, key, timeout: Optional[float] = None) -> bytes:
        self._chk(key)
        return self._base.get(key, timeout)

    def try_get(self, key) -> Optional[bytes]:
        self._chk(key)
        return self._base.try_get(key)

    def add(self, key, amount: int = 1) -> int:
        self._chk(key)
        return self._base.add(key, amount)

    def append(self, key, value) -> int:
        self._chk(key)
        return self._base.append(key, value)

    def compare_set(self, key, expected, desired) -> bytes:
        self._chk(key)
        return self._base.compare_set(key, expected, desired)

    def compare_set_ex(self, key, expected, desired) -> Tuple[bool, bytes]:
        self._chk(key)
        return self._base.compare_set_ex(key, expected, desired)

    def wait(self, keys: Sequence, timeout: Optional[float] = None) -> None:
        self._chk(*keys)
        return self._base.wait(keys, timeout)

    def check(self, keys: Sequence) -> bool:
        self._chk(*keys)
        return self._base.check(keys)

    def delete(self, key) -> bool:
        self._chk(key)
        return self._base.delete(key)

    def multi_set(self, items: dict) -> None:
        self._chk(*items.keys())
        return self._base.multi_set(items)

    def multi_get(self, keys: Sequence) -> List[Optional[bytes]]:
        self._chk(*keys)
        return self._base.multi_get(keys)

    def append_check(
        self, key, value, done_key, done_value,
        required: int = 0, tokens: Sequence = (),
    ) -> Tuple[int, bool]:
        self._chk(key, done_key)
        return self._base.append_check(
            key, value, done_key, done_value, required, tokens
        )

    def add_set(self, add_key, amount: int, set_key, set_value) -> int:
        self._chk(add_key, set_key)
        return self._base.add_set(add_key, amount, set_key, set_value)

    def wait_ge(self, key, threshold: int,
                timeout: Optional[float] = None) -> int:
        self._chk(key)
        return self._base.wait_ge(key, threshold, timeout)


class ShardedStoreFactory:
    """Picklable ``() -> ShardedStoreClient`` factory (the sharded analog of
    :class:`~tpu_resiliency.store.client.StoreFactory` — spawn-safe for
    subprocess helpers that cannot pickle a lambda)."""

    def __init__(self, endpoints, timeout: float = _DEFAULT_TIMEOUT, **kwargs):
        self.endpoints = [
            f"{h}:{p}" for h, p in _parse_endpoints(endpoints)
        ]
        self.timeout = timeout
        self.kwargs = kwargs

    def __call__(self) -> ShardedStoreClient:
        return ShardedStoreClient(
            self.endpoints, timeout=self.timeout, **self.kwargs
        )


# -- hosting helpers ---------------------------------------------------------


def free_port(host: str = "127.0.0.1") -> int:
    """A currently-free TCP port (picked-then-released: a tiny race window
    that shard spawners accept in exchange for announcing ports up front)."""
    s = socket.socket()
    try:
        s.bind((host, 0))
        return s.getsockname()[1]
    finally:
        s.close()


class ShardServerGroup:
    """K in-process asyncio shards (tests, single-host control planes).

    Each shard gets its own journal (``<base>.shard<i>``) so any one can be
    killed and journal-replayed independently.  The shard map is published
    on shard 0 (the bootstrap seed) once the fleet is listening.
    """

    def __init__(
        self,
        n_shards: int,
        host: str = "127.0.0.1",
        journal_base: Optional[str] = None,
        journal_max_bytes: int = 64 << 20,
    ):
        from .server import StoreServer

        if n_shards < 1:
            raise ValueError("need at least one shard")
        self.servers = [
            StoreServer(
                host=host,
                port=0,
                journal_path=(
                    f"{journal_base}.shard{i}" if journal_base else None
                ),
                journal_max_bytes=journal_max_bytes,
            )
            for i in range(n_shards)
        ]

    @property
    def endpoints(self) -> List[str]:
        return [f"{s.host}:{s.port}" for s in self.servers]

    def start(self) -> "ShardServerGroup":
        for s in self.servers:
            s.start_in_thread()
        seed = StoreClient(self.servers[0].host, self.servers[0].port)
        try:
            publish_shard_map(seed, ShardMap(self.endpoints))
        finally:
            seed.close()
        return self

    def client(self, timeout: float = _DEFAULT_TIMEOUT) -> ShardedStoreClient:
        return ShardedStoreClient(self.endpoints, timeout=timeout)

    def stop(self) -> None:
        for s in self.servers:
            s.stop()


def spawn_shard_subprocess(
    port: int,
    host: str = "127.0.0.1",
    journal: Optional[str] = None,
    journal_max_bytes: Optional[int] = None,
    env: Optional[dict] = None,
    connect_timeout: float = 20.0,
) -> subprocess.Popen:
    """One shard as a separate OS process (SIGKILL-able fault-injection
    target; a core of its own in production layouts).  Blocks until the
    shard accepts connections."""
    cmd = [
        sys.executable, "-m", "tpu_resiliency.store.server",
        "--host", host, "--port", str(port),
    ]
    if journal:
        cmd += ["--journal", journal]
    if journal_max_bytes is not None:
        cmd += ["--journal-max-bytes", str(journal_max_bytes)]
    proc = subprocess.Popen(
        cmd,
        env={**os.environ, **(env or {})},
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )
    retrier = Retrier("shard_spawn", _SPAWN_POLL, deadline=connect_timeout)
    while True:
        if proc.poll() is not None:
            raise RuntimeError(
                f"shard subprocess on port {port} exited at startup "
                f"(rc={proc.returncode})"
            )
        try:
            StoreClient(host, port, connect_timeout=1.0).close()
            return proc
        except StoreError as exc:
            try:
                retrier.backoff(exc)
            except RetryExhausted:
                proc.kill()
                raise RuntimeError(
                    f"shard subprocess on port {port} never accepted"
                ) from exc
