"""Opt-in runtime lock-order sanitizer (``TPURX_SANITIZE=1``).

The static lock-order rule (tpurx-lint TPURX011) reasons about (class, attr)
lock identities and can only say PLAUSIBLE — per-instance aliasing is not
provable from source.  This module closes the loop from the runtime side:
``install()`` swaps ``threading.Lock``/``threading.RLock`` for tracking
wrappers (stdlib ``Condition``/``Event``/``queue`` resolve those names at
call time, so they are covered transitively), records the ACTUAL
cross-thread acquisition DAG, and

- **trips loudly** the moment a thread's acquisition would close a cycle
  over concrete lock objects — i.e. one scheduler interleaving away from
  deadlock — by raising :class:`LockOrderViolation` *before* the acquire
  can park (the classic lock-order-sanitizer move: report the inversion,
  don't demonstrate the deadlock);
- writes each distinct (held, acquired) edge once to a JSONL **witness
  file**, keyed by each lock's creation site — the same site the static
  lock table indexes, so ``tpurx-lint --witness <file>`` can promote
  PLAUSIBLE static cycles to CONFIRMED or prune ones the runtime only ever
  observed in one consistent order.

Re-acquiring a held RLock is reentrant and never an edge; re-acquiring a
held non-reentrant Lock on the same object is a guaranteed self-deadlock
and trips immediately.  Locks created before ``install()`` are untracked
(install early — the package ``__init__`` does it when the knob is set).
"""

from __future__ import annotations

import _thread
import atexit
import json
import os
import sys
import threading

from . import env

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_SKIP_FILES = (os.sep + "threading.py", os.sep + "sanitize.py",
               os.sep + "dataclasses.py")


class LockOrderViolation(RuntimeError):
    """Acquiring this lock would close a lock-order cycle (or re-acquire a
    held non-reentrant Lock): one scheduler interleaving away from deadlock."""


class _State:
    """Process-global sanitizer state.  Guarded by a RAW ``_thread`` lock so
    the sanitizer's own bookkeeping is invisible to itself."""

    def __init__(self):
        self.mu = _thread.allocate_lock()
        self.site_edges = set()      # ((site, kind), (site, kind))
        self.obj_edges = {}          # uid -> set(uid)
        self.uid_site = {}           # uid -> (site, kind)
        self.next_uid = 0
        self.witness_fh = None
        self.witness_path = None
        self.cycles = 0
        self.edges_written = 0
        self.local = threading.local()

    def held(self):
        stack = getattr(self.local, "stack", None)
        if stack is None:
            stack = self.local.stack = []
        return stack


_S = _State()
_ORIG = {}                 # name -> original factory
_INSTALLED = False


def _caller_site() -> str:
    """file:line of the first frame outside threading/sanitize machinery,
    repo-relative when under the repo root (matches the static lock table)."""
    f = sys._getframe(2)
    while f is not None:
        fn = f.f_code.co_filename
        if not fn.endswith(_SKIP_FILES):
            if fn.startswith(_REPO_ROOT):
                fn = os.path.relpath(fn, _REPO_ROOT).replace(os.sep, "/")
            return f"{fn}:{f.f_lineno}"
        f = f.f_back
    return "<unknown>:0"


def _emit(rec: dict) -> None:
    fh = _S.witness_fh
    if fh is not None:
        try:
            fh.write(json.dumps(rec, separators=(",", ":")) + "\n")
        except (OSError, ValueError):
            pass


def _find_path(frm: int, to: int):
    """Site chain if `to` is reachable from `frm` over object edges."""
    stack = [(frm, [frm])]
    seen = set()
    while stack:
        node, path = stack.pop()
        if node == to:
            return [_S.uid_site.get(u, ("<stale>",))[0] for u in path]
        if node in seen:
            continue
        seen.add(node)
        for nxt in _S.obj_edges.get(node, ()):
            stack.append((nxt, path + [nxt]))
    return None


class _TrackedLock:
    """Wrapper around a raw lock/RLock recording acquisition order."""

    _reentrant = False

    def __init__(self, inner, site: str, kind: str):
        self._inner = inner
        self._site = site
        self._kind = kind
        with _S.mu:
            self._uid = _S.next_uid
            _S.next_uid += 1
            _S.uid_site[self._uid] = (site, kind)

    # -- bookkeeping -------------------------------------------------------

    def _check_order(self, blocking) -> None:
        held = _S.held()
        if not held:
            return
        if self in held:
            if self._reentrant:
                return
            if blocking:
                rec = {"event": "cycle", "kind": "self",
                       "chain": [self._site, self._site],
                       "thread": threading.current_thread().name}
                with _S.mu:
                    _S.cycles += 1
                    _emit(rec)
                raise LockOrderViolation(
                    f"re-acquiring held non-reentrant Lock created at "
                    f"{self._site} in thread "
                    f"{threading.current_thread().name}: guaranteed "
                    f"self-deadlock")
            return
        with _S.mu:
            for h in held:
                if h is self:
                    continue
                key = ((h._site, h._kind), (self._site, self._kind))
                if key not in _S.site_edges:
                    _S.site_edges.add(key)
                    _S.edges_written += 1
                    _emit({"event": "edge",
                           "frm": {"site": h._site, "kind": h._kind},
                           "to": {"site": self._site, "kind": self._kind},
                           "thread": threading.current_thread().name,
                           "at": _caller_site()})
                peers = _S.obj_edges.setdefault(h._uid, set())
                if self._uid not in peers:
                    # would h be reachable FROM self? then h->self closes a
                    # concrete-object cycle: the inversion a deadlock needs
                    chain = _find_path(self._uid, h._uid)
                    if chain is not None and blocking:
                        full = [h._site] + chain
                        _S.cycles += 1
                        _emit({"event": "cycle", "kind": "order",
                               "chain": full,
                               "thread": threading.current_thread().name})
                        raise LockOrderViolation(
                            f"lock-order cycle: acquiring lock created at "
                            f"{self._site} while holding {h._site}, but the "
                            f"reverse order was already observed "
                            f"(chain: {' -> '.join(full)})")
                    peers.add(self._uid)

    def _did_acquire(self) -> None:
        _S.held().append(self)

    def _did_release(self) -> None:
        held = _S.held()
        for i in range(len(held) - 1, -1, -1):
            if held[i] is self:
                del held[i]
                return

    # -- lock protocol -----------------------------------------------------

    def acquire(self, blocking=True, timeout=-1):
        if blocking:
            self._check_order(timeout in (-1, None))
        ok = self._inner.acquire(blocking, timeout)
        if ok:
            self._did_acquire()
        return ok

    def release(self):
        self._inner.release()
        self._did_release()

    def locked(self):
        return self._inner.locked()

    def _at_fork_reinit(self):
        # stdlib (concurrent.futures, logging, threading._after_fork) calls
        # this on module-level locks in the forked child
        self._inner._at_fork_reinit()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def __repr__(self):
        return f"<tpurx-sanitized {self._kind} @{self._site} {self._inner!r}>"


class _TrackedRLock(_TrackedLock):
    _reentrant = True

    # Condition integration: these three are how Condition.wait releases and
    # re-takes the lock — routing them through the wrapper keeps the held
    # stack truthful across the wait (parked = not holding).

    def _release_save(self):
        state = self._inner._release_save()
        held = _S.held()
        for i in range(len(held) - 1, -1, -1):
            if held[i] is self:
                del held[i]
        return state

    def _acquire_restore(self, state):
        self._inner._acquire_restore(state)
        self._did_acquire()

    def _is_owned(self):
        return self._inner._is_owned()

    def _recursion_count(self):
        # multiprocessing.resource_tracker (3.12.4+) asks its RLock this to
        # refuse re-entrant calls from a GC'd finalizer
        return self._inner._recursion_count()


def _make_factory(kind: str):
    orig = _ORIG[kind]
    wrapper_cls = _TrackedRLock if kind == "RLock" else _TrackedLock

    def factory():
        return wrapper_cls(orig(), _caller_site(), kind)

    factory.__name__ = f"tpurx_sanitized_{kind}"
    return factory


def _after_fork_in_child() -> None:
    _S.mu = _thread.allocate_lock()
    _S.local = threading.local()


def install(witness_path: str | None = None) -> None:
    """Patch ``threading.Lock``/``threading.RLock`` with tracking factories
    and (optionally) open the JSONL witness sink.  Idempotent."""
    global _INSTALLED
    if _INSTALLED:
        return
    _ORIG["Lock"] = threading.Lock
    _ORIG["RLock"] = threading.RLock
    threading.Lock = _make_factory("Lock")
    threading.RLock = _make_factory("RLock")
    # fork hygiene: the child inherits the parent's held-stacks and possibly
    # a mid-critical-section state lock — reinitialize both (observed edges
    # are kept; they remain true observations from the parent)
    os.register_at_fork(after_in_child=_after_fork_in_child)
    if witness_path:
        path = witness_path.replace("%p", str(os.getpid()))
        path = path.replace("%r", str(env.RANK.get()))
        _S.witness_path = path
        _S.witness_fh = open(path, "a", buffering=1)
        _emit({"event": "meta", "pid": os.getpid(),
               "rank": env.RANK.get(), "version": 1})
        atexit.register(close_witness)
    _INSTALLED = True


def uninstall() -> None:
    """Restore the original factories (already-wrapped locks stay wrapped)."""
    global _INSTALLED
    if not _INSTALLED:
        return
    threading.Lock = _ORIG.pop("Lock")
    threading.RLock = _ORIG.pop("RLock")
    close_witness()
    _INSTALLED = False


def close_witness() -> None:
    fh, _S.witness_fh = _S.witness_fh, None
    if fh is not None:
        try:
            fh.close()
        except OSError:
            pass


def install_from_env() -> bool:
    """Install when ``TPURX_SANITIZE`` is set; returns whether installed."""
    if not env.SANITIZE.get():
        return False
    install(witness_path=env.SANITIZE_WITNESS_PATH.get())
    return True


def stats() -> dict:
    with _S.mu:
        return {
            "installed": _INSTALLED,
            "locks": _S.next_uid,
            "edges": len(_S.site_edges),
            "cycles": _S.cycles,
            "witness_path": _S.witness_path,
        }


def reset_for_tests() -> None:
    """Drop recorded state (NOT the patch) so unit tests are independent."""
    with _S.mu:
        _S.site_edges.clear()
        _S.obj_edges.clear()
        _S.uid_site.clear()
        _S.next_uid = 0
        _S.cycles = 0
    _S.local = threading.local()
