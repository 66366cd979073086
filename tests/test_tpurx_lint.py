"""tpurx-lint framework tests: per-rule firing/passing fixtures, suppression
discipline, baseline round-trip, and the tier-1 repo gate.

Fixture snippets are written into a throwaway tree mirroring the repo layout
(`<tmp>/tpu_resiliency/...`) because every rule scopes by repo-relative path.
"""

import json
import os
import textwrap
import time

import pytest

from tpurx_lint import run_lint
from tpurx_lint.baseline import Baseline
from tpurx_lint.registry import all_rules

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def lint_snippet(tmp_path, rel, code, rule=None, extra_files=()):
    """Write `code` at `<tmp>/<rel>` and lint it; returns finding list."""
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(code))
    for erel, ecode in extra_files:
        epath = tmp_path / erel
        epath.parent.mkdir(parents=True, exist_ok=True)
        epath.write_text(textwrap.dedent(ecode))
    result = run_lint(paths=[str(tmp_path)], root=str(tmp_path),
                      use_baseline=False,
                      rule_ids=[rule] if rule else None)
    return result.findings


def rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# rule registry basics
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_sixteen_rules_with_stable_ids(self):
        ids = [r.rule_id for r in all_rules()]
        assert ids == [f"TPURX{n:03d}" for n in range(1, 17)]

    def test_every_rule_documents_itself(self):
        for r in all_rules():
            assert r.name and r.rationale and r.scope, r.rule_id


# ---------------------------------------------------------------------------
# migrated bans (TPURX001-004): one firing + one passing case each
# ---------------------------------------------------------------------------

class TestBarePrint:
    def test_fires(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/mod.py",
                          "print('hi')\n", rule="TPURX001")
        assert rules_of(fs) == {"TPURX001"}

    def test_passes_logger_and_out_of_scope(self, tmp_path):
        assert not lint_snippet(tmp_path, "tpu_resiliency/mod.py",
                                "import logging\nlogging.info('hi')\n",
                                rule="TPURX001")
        # scripts outside the library may print
        assert not lint_snippet(tmp_path, "examples/x.py", "print('hi')\n",
                                rule="TPURX001")


class TestRawCkptRead:
    def test_fires_on_rb_open_and_os_pread(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/checkpointing/x.py", """
            import os
            def f(p, fd):
                with open(p, "rb") as fh:
                    fh.read()
                os.pread(fd, 10, 0)
        """, rule="TPURX002")
        assert len(fs) == 2

    def test_passes_in_integrity_and_write_mode(self, tmp_path):
        assert not lint_snippet(
            tmp_path, "tpu_resiliency/checkpointing/integrity.py",
            'x = open("p", "rb")\n', rule="TPURX002")
        assert not lint_snippet(
            tmp_path, "tpu_resiliency/checkpointing/x.py",
            'x = open("p", "wb")\n', rule="TPURX002")


class TestWallClockStamp:
    def test_fires(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            import time
            last_heartbeat = time.time()
        """, rule="TPURX003")
        assert rules_of(fs) == {"TPURX003"}

    def test_passes_non_stamp_and_quorum_home(self, tmp_path):
        assert not lint_snippet(tmp_path, "tpu_resiliency/mod.py",
                                "import time\nstarted = time.time()\n",
                                rule="TPURX003")
        assert not lint_snippet(tmp_path, "tpu_resiliency/ops/quorum.py",
                                "import time\nstamp = time.time()\n",
                                rule="TPURX003")


class TestFlatGather:
    def test_fires_on_loop_and_multiget(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            def f(store, world_size):
                out = [store.get(f"k/{r}") for r in range(2)]
                for r in range(world_size):
                    out.append(store.try_get(f"k/{r}"))
                store.multi_get([f"k/{r}" for r in range(world_size)])
                return out
        """, rule="TPURX004")
        assert len(fs) == 2  # loop-read + multi_get comprehension

    def test_passes_in_tree_helper(self, tmp_path):
        assert not lint_snippet(tmp_path, "tpu_resiliency/store/tree.py", """
            def f(store, world_size):
                return [store.get(f"k/{r}") for r in range(world_size)]
        """, rule="TPURX004")


# ---------------------------------------------------------------------------
# deep checkers (TPURX005-010)
# ---------------------------------------------------------------------------

class TestDeadlineDiscipline:
    def test_fires_on_unbounded_waits(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            import subprocess
            def f(ev, t, proc):
                ev.wait()
                t.join()
                proc.communicate()
                subprocess.run(["x"])
        """, rule="TPURX005")
        assert len(fs) == 4

    def test_passes_with_bounds(self, tmp_path):
        assert not lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            import asyncio
            import subprocess
            async def f(ev, t, proc, timeout):
                ev.wait(5.0)
                ev.wait(timeout=timeout)
                t.join(timeout=30)
                proc.communicate(timeout=10)
                subprocess.run(["x"], timeout=60)
                ",".join(["a", "b"])          # str.join has an argument
                await asyncio.wait_for(ev.wait(), timeout=1.0)
        """, rule="TPURX005")

    def test_timeout_none_is_unbounded(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/mod.py",
                          "def f(ev):\n    ev.wait(timeout=None)\n",
                          rule="TPURX005")
        assert len(fs) == 1

    def test_fires_on_raw_socket_recv_without_bound(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            def f(sock, conn):
                a = sock.recv(4096)
                b = conn.recv_into(bytearray(16))
                return a, b
        """, rule="TPURX005")
        assert len(fs) == 2
        assert all("socket wait blocks async raises" in f.message for f in fs)

    def test_passes_recv_with_deadline_intent_in_scope(self, tmp_path):
        # intent, not value: a finite settimeout / poll gate anywhere in the
        # enclosing function (or a timeout= kw on a recv wrapper) bounds it
        assert not lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            def f(sock, conn, exchange, t):
                sock.settimeout(t)
                a = sock.recv(4096)
                if conn.poll(0.25):
                    b = conn.recv(16)
                c = exchange.recv(1, 2, timeout=t)
                return a, b, c
        """, rule="TPURX005")

    def test_recv_sanctioned_in_store_io_core(self, tmp_path):
        # store/client.py IS the interruptible I/O core: its recv loops
        # are quantum-sliced by construction
        assert not lint_snippet(tmp_path, "tpu_resiliency/store/client.py", """
            def f(sock):
                return sock.recv(4096)
        """, rule="TPURX005")

    def test_recv_bufsize_is_not_a_timeout(self, tmp_path):
        # the positional arg of recv is a byte count; it must not satisfy
        # the bound check the way a positional timeout does for wait()
        fs = lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            def f(sock):
                return sock.recv(65536)
        """, rule="TPURX005")
        assert len(fs) == 1

    def test_create_connection_needs_timeout(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            import socket
            def f():
                a = socket.create_connection(("h", 1))
                b = socket.create_connection(("h", 1), timeout=2.0)
                return a, b
        """, rule="TPURX005")
        assert len(fs) == 1


class TestAbortPathSafety:
    def test_fires_in_abort_stage_and_signal_handler(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/inprocess/x.py", """
            import signal
            import threading

            class AbortStage:
                pass

            class MyStage(AbortStage):
                def release(self, state=None):
                    self._helper()

                def _helper(self):
                    threading.Thread(target=print).start()

            def _handler(signum, frame):
                import subprocess
                subprocess.run(["cleanup"])

            signal.signal(signal.SIGTERM, _handler)
        """, rule="TPURX006")
        msgs = [f.message for f in fs]
        assert any("thread spawned" in m for m in msgs)
        assert any("signal handler" in m for m in msgs)

    def test_passes_bounded_stage(self, tmp_path):
        assert not lint_snippet(tmp_path, "tpu_resiliency/inprocess/x.py", """
            class AbortStage:
                pass

            class MyStage(AbortStage):
                def release(self, state=None):
                    state.proc.wait(timeout=5.0)
        """, rule="TPURX006")


class TestRetryDiscipline:
    def test_fires_on_hand_rolled_loop(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            import time
            def f(connect):
                while True:
                    try:
                        return connect()
                    except OSError:
                        time.sleep(1.0)
        """, rule="TPURX007")
        assert rules_of(fs) == {"TPURX007"}

    def test_passes_poll_loop_and_retry_home(self, tmp_path):
        # a forever poll loop (no success escape in the try) is not a retry
        assert not lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            import time
            def monitor(tick):
                while True:
                    try:
                        tick()
                    except OSError:
                        pass
                    time.sleep(1.0)
        """, rule="TPURX007")
        assert not lint_snippet(tmp_path, "tpu_resiliency/utils/retry.py", """
            import time
            def f(connect):
                while True:
                    try:
                        return connect()
                    except OSError:
                        time.sleep(1.0)
        """, rule="TPURX007")


class TestThreadLifecycle:
    def test_fires_on_leaked_thread(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            import threading
            def f():
                t = threading.Thread(target=print)
                t.start()
        """, rule="TPURX008")
        assert rules_of(fs) == {"TPURX008"}

    def test_passes_daemon_or_joined(self, tmp_path):
        assert not lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            import threading
            def f():
                threading.Thread(target=print, daemon=True).start()
                t = threading.Thread(target=print)
                t.start()
                t.join(timeout=5.0)
        """, rule="TPURX008")

    def test_guarded_by_fires_outside_lock(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            import threading
            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0  # guarded-by: _lock

                def bump(self):
                    self._n += 1
        """, rule="TPURX008")
        assert any("guarded-by" in f.message for f in fs)

    def test_guarded_by_passes_under_lock(self, tmp_path):
        assert not lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            import threading
            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0  # guarded-by: _lock

                def bump(self):
                    with self._lock:
                        self._n += 1
        """, rule="TPURX008")


class TestExceptionHygiene:
    def test_fires_on_swallow_and_bare(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/inprocess/x.py", """
            def f(g):
                try:
                    g()
                except Exception:
                    pass
                try:
                    g()
                except:
                    raise
        """, rule="TPURX009")
        assert len(fs) == 2

    def test_passes_narrow_or_logged(self, tmp_path):
        assert not lint_snippet(tmp_path, "tpu_resiliency/inprocess/x.py", """
            import logging
            def f(g):
                try:
                    g()
                except OSError:
                    pass
                try:
                    g()
                except Exception as exc:
                    logging.warning("failed: %r", exc)
        """, rule="TPURX009")

    def test_swallow_allowed_outside_fault_tree(self, tmp_path):
        # integrations/ is not a fault-handling tree; only bare except fires
        assert not lint_snippet(
            tmp_path, "tpu_resiliency/integrations/x.py",
            "def f(g):\n    try:\n        g()\n    except Exception:\n        pass\n",
            rule="TPURX009")


_ENV_FIXTURE = [
    ("tpu_resiliency/utils/env.py", """
        class Knob:
            def __init__(self, name, type, default, doc):
                self.name = name
        FOO = Knob("TPURX_FOO", int, 1, "doc")
    """),
    ("docs/configuration.md", "| `TPURX_FOO` | int | `1` | doc |\n"),
]


class TestEnvRegistry:
    def test_fires_on_raw_read(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            import os
            x = os.environ.get("TPURX_FOO", "1")
            y = os.getenv("TPURX_BAR")
            z = os.environ["TPURX_BAZ"]
            present = "TPURX_QUX" in os.environ
        """, rule="TPURX010", extra_files=_ENV_FIXTURE)
        assert len(fs) == 4

    def test_resolves_env_constant_idiom(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            import os
            ENV_FOO = "TPURX_FOO"
            x = os.environ.get(ENV_FOO)
        """, rule="TPURX010", extra_files=_ENV_FIXTURE)
        assert len(fs) == 1

    def test_passes_registry_read_and_non_tpurx(self, tmp_path):
        assert not lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            import os
            from .utils import env
            x = env.FOO.get()
            home = os.environ.get("HOME")
        """, rule="TPURX010", extra_files=_ENV_FIXTURE)

    def test_fires_on_direct_write(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            import os
            os.environ["TPURX_FOO"] = "1"
            os.environ.setdefault("TPURX_BAR", "1")
            os.environ.pop("TPURX_BAZ", None)
            os.putenv("TPURX_QUX", "1")
            os.environ.update({"TPURX_QUUX": "1"})
        """, rule="TPURX010", extra_files=_ENV_FIXTURE)
        assert len([f for f in fs if "direct os.environ write" in f.message]) \
            == 5

    def test_policy_package_is_sanctioned_writer(self, tmp_path):
        assert not lint_snippet(
            tmp_path, "tpu_resiliency/policy/actuator.py", """
                import os
                os.environ["TPURX_FOO"] = "1"
            """, rule="TPURX010", extra_files=_ENV_FIXTURE)

    def test_identity_republication_is_exempt(self, tmp_path):
        # the launcher restamps rank identity after a mesh shrink; children
        # inherit it through the real environment, so the write is legal
        assert not lint_snippet(
            tmp_path, "tpu_resiliency/inprocess/state.py", """
                import os
                os.environ["TPURX_RANK"] = "0"
                os.environ["TPURX_WORLD_SIZE"] = "4"
            """, rule="TPURX010", extra_files=_ENV_FIXTURE)

    def test_write_through_constant_idiom_fires(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            import os
            ENV_FOO = "TPURX_FOO"
            os.environ[ENV_FOO] = "1"
        """, rule="TPURX010", extra_files=_ENV_FIXTURE)
        assert len(fs) == 1 and "direct os.environ write" in fs[0].message

    def test_repurposed_exempt_key_loses_waiver(self, tmp_path):
        # TPURX_RANK declared as a plain tuning knob (not identity-group,
        # no publisher doc) -> the WRITE_EXEMPT entry no longer qualifies
        fs = lint_snippet(
            tmp_path, "tpu_resiliency/utils/env.py", """
                class Knob:
                    def __init__(self, name, type, default, doc, group="g"):
                        self.name = name
                RANK = Knob("TPURX_RANK", int, 0, "doc", group="tuning")
            """, rule="TPURX010",
            extra_files=[("docs/configuration.md", "`TPURX_RANK`\n")])
        assert any("no longer qualifies" in f.message for f in fs)

    def test_undocumented_knob_fails(self, tmp_path):
        fs = lint_snippet(
            tmp_path, "tpu_resiliency/utils/env.py", """
                class Knob:
                    def __init__(self, name, type, default, doc):
                        self.name = name
                FOO = Knob("TPURX_FOO", int, 1, "doc")
                BAR = Knob("TPURX_BAR", int, 2, "doc")
            """, rule="TPURX010",
            extra_files=[("docs/configuration.md", "only `TPURX_FOO` here\n")])
        assert any("TPURX_BAR" in f.message and "not documented" in f.message
                   for f in fs)

    def test_duplicate_declaration_fails(self, tmp_path):
        fs = lint_snippet(
            tmp_path, "tpu_resiliency/utils/env.py", """
                class Knob:
                    def __init__(self, name, type, default, doc):
                        self.name = name
                A = Knob("TPURX_FOO", int, 1, "doc")
                B = Knob("TPURX_FOO", int, 2, "doc")
            """, rule="TPURX010",
            extra_files=[("docs/configuration.md", "`TPURX_FOO`\n")])
        assert any("declared more than once" in f.message for f in fs)


# ---------------------------------------------------------------------------
# whole-program tier (TPURX011-013) — see test_lockorder_analysis.py for the
# deep call-graph/lock-order fixtures; these are the one-firing/one-passing
# cases the rule-addition checklist requires
# ---------------------------------------------------------------------------

class TestLockOrder:
    def test_fires_on_intra_class_inversion(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            import threading

            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def one(self):
                    with self._a:
                        with self._b:
                            pass

                def two(self):
                    with self._b:
                        with self._a:
                            pass
        """, rule="TPURX011")
        assert rules_of(fs) == {"TPURX011"}
        assert any("PLAUSIBLE" in f.message and "deadlock" in f.message
                   for f in fs)

    def test_passes_consistent_order(self, tmp_path):
        assert not lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            import threading

            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def one(self):
                    with self._a:
                        with self._b:
                            pass

                def two(self):
                    with self._a:
                        with self._b:
                            pass
        """, rule="TPURX011")


class TestDeadlinePropagation:
    def test_fires_on_dead_and_dropped_deadline(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            class C:
                def join(self, timeout):
                    self._cv.wait()
        """, rule="TPURX012")
        msgs = [f.message for f in fs]
        assert any("never reads it" in m for m in msgs)
        assert any("drops it" in m for m in msgs)

    def test_passes_threaded_deadline(self, tmp_path):
        assert not lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            class C:
                def join(self, timeout):
                    self._cv.wait(timeout=timeout)
        """, rule="TPURX012")

    def test_fires_on_call_site_drop(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            def blocking_helper(timeout=None):
                ev().wait(timeout=timeout)

            def outer(deadline):
                x = deadline  # read, so no dead-deadline finding
                blocking_helper()
        """, rule="TPURX012")
        assert len(fs) == 1
        assert "stops propagating" in fs[0].message

    def test_passes_call_site_bound(self, tmp_path):
        assert not lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            def blocking_helper(timeout=None):
                ev().wait(timeout=timeout)

            def outer(deadline):
                blocking_helper(timeout=deadline)
        """, rule="TPURX012")


class TestStoreKeyLifecycle:
    def test_fires_on_undeleted_round_key(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/store/proto.py", """
            def publish(store, round_no, rank):
                store.set(f"round/{round_no}/r{rank}", b"1")
        """, rule="TPURX013")
        assert rules_of(fs) == {"TPURX013"}
        assert "round" in fs[0].message

    def test_passes_with_delete_path_and_singleton(self, tmp_path):
        assert not lint_snippet(tmp_path, "tpu_resiliency/store/proto.py", """
            def publish(store, round_no, rank):
                store.set(f"round/{round_no}/r{rank}", b"1")
                store.set("round_singleton", b"1")

            def gc(store, round_no, rank):
                store.delete(f"round/{round_no}/r{rank}")
        """, rule="TPURX013")

    def test_append_on_fixed_key_still_fires(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/store/proto.py", """
            def log(store, rank):
                store.append("audit_log", f"{rank},")
        """, rule="TPURX013")
        assert rules_of(fs) == {"TPURX013"}


class TestRawCollective:
    def test_fires_on_allgather_and_lax(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            import jax
            from jax import lax
            from jax.experimental import multihost_utils

            def f(x, axis):
                vals = multihost_utils.process_allgather(x)
                a = lax.pmax(x, axis)
                b = jax.lax.ppermute(x, axis, perm=[(0, 1)])
                return vals, a, b
        """, rule="TPURX014")
        assert rules_of(fs) == {"TPURX014"}
        assert len(fs) == 3
        msgs = " ".join(f.message for f in fs)
        assert "ResilientCollective" in msgs

    def test_passes_in_wrapper_home_and_quorum_lane(self, tmp_path):
        # parallel/collectives.py is the sanctioned home for raw collectives
        assert not lint_snippet(
            tmp_path, "tpu_resiliency/parallel/collectives.py", """
                from jax import lax
                from jax.experimental import multihost_utils

                def f(x, axis):
                    return multihost_utils.process_allgather(x), lax.pmax(x, axis)
            """, rule="TPURX014")
        # ops/quorum.py's jitted detection lane is allowlisted
        assert not lint_snippet(tmp_path, "tpu_resiliency/ops/quorum.py", """
            import jax

            def f(x, axis):
                return jax.lax.pmax(x, axis)
        """, rule="TPURX014")

    def test_passes_non_collective_lax_and_out_of_scope(self, tmp_path):
        # lax math primitives are not collectives
        assert not lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            from jax import lax

            def f(x):
                return lax.cumsum(x, axis=0)
        """, rule="TPURX014")
        # scripts outside the library may call raw collectives
        assert not lint_snippet(tmp_path, "examples/x.py", """
            from jax.experimental import multihost_utils

            def f(x):
                return multihost_utils.process_allgather(x)
        """, rule="TPURX014")


class TestRawDeviceRead:
    def test_fires_on_raw_d2h(self, tmp_path):
        fs = lint_snippet(
            tmp_path, "tpu_resiliency/checkpointing/capture.py", """
            import jax

            def grab(tree, shard):
                shard.data.copy_to_host_async()
                return jax.device_get(tree)
        """, rule="TPURX015")
        assert rules_of(fs) == {"TPURX015"}
        assert len(fs) == 2
        assert "staging" in " ".join(f.message for f in fs)

    def test_passes_in_staging_layer_and_out_of_scope(self, tmp_path):
        # staging.py and device_digest.py are the sanctioned touchpoints
        for home in (
            "tpu_resiliency/checkpointing/async_ckpt/staging.py",
            "tpu_resiliency/checkpointing/async_ckpt/device_digest.py",
        ):
            assert not lint_snippet(tmp_path, home, """
                import jax

                def kick(shard):
                    shard.data.copy_to_host_async()
                    return jax.device_get(shard.data)
            """, rule="TPURX015")
        # non-checkpoint code may read devices freely
        assert not lint_snippet(tmp_path, "tpu_resiliency/health/probe.py", """
            import jax

            def probe(x):
                return jax.device_get(x)
        """, rule="TPURX015")

    def test_sanctioned_kick_passes(self, tmp_path):
        assert not lint_snippet(
            tmp_path, "tpu_resiliency/checkpointing/local/cap.py", """
            from ..async_ckpt.staging import async_d2h

            def grab(shards):
                async_d2h(s.data for s in shards)
        """, rule="TPURX015")


class TestWallClockDuration:
    def test_fires_on_direct_subtraction(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            import time

            def f(t0):
                return time.time() - t0
        """, rule="TPURX016")
        assert rules_of(fs) == {"TPURX016"}

    def test_fires_on_assigned_name_used_in_subtraction(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            import time

            def f(stamp):
                now = time.time()
                return now - stamp
        """, rule="TPURX016")
        assert len(fs) == 1

    def test_fires_on_datetime_now(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            import datetime

            def f(started):
                return datetime.datetime.now() - started
        """, rule="TPURX016")
        assert rules_of(fs) == {"TPURX016"}

    def test_passes_monotonic_and_labels(self, tmp_path):
        assert not lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            import time

            def f(t0):
                dur = time.monotonic_ns() - t0
                return {"dur": dur, "ts": time.time()}
        """, rule="TPURX016")

    def test_wall_name_in_one_function_does_not_taint_another(self, tmp_path):
        # `now` is wall-clock in f but monotonic in g: only f may fire
        fs = lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            import time

            def f(t):
                now = time.time()
                return now - t

            def g(t):
                now = time.monotonic()
                return now - t
        """, rule="TPURX016")
        assert len(fs) == 1

    def test_allowlisted_file_and_out_of_scope_pass(self, tmp_path):
        snippet = """
            import time

            def age(m):
                return time.time() - m.ts
        """
        assert not lint_snippet(
            tmp_path, "tpu_resiliency/attribution/trace_analyzer.py",
            snippet, rule="TPURX016")
        assert not lint_snippet(
            tmp_path, "examples/x.py", snippet, rule="TPURX016")


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

class TestSuppressions:
    def test_same_line_suppression_with_reason(self, tmp_path):
        assert not lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            def f(ev):
                ev.wait()  # tpurx: disable=TPURX005 -- sentinel always arrives
        """, rule="TPURX005")

    def test_comment_above_covers_next_line(self, tmp_path):
        assert not lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            def f(ev):
                # tpurx: disable=TPURX005 -- sentinel always arrives
                ev.wait()
        """, rule="TPURX005")

    def test_suppression_without_reason_is_a_finding(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            def f(ev):
                ev.wait()  # tpurx: disable=TPURX005
        """)
        assert "TPURX900" in rules_of(fs)
        # and the original finding is NOT suppressed by a reasonless directive
        assert "TPURX005" in rules_of(fs)

    def test_file_scope_suppression(self, tmp_path):
        assert not lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            # tpurx: disable-file=TPURX001 -- argparse CLI, stdout is the interface
            print("usage: ...")
            print("more")
        """, rule="TPURX001")

    def test_wrong_rule_suppression_does_not_mask(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            def f(ev):
                ev.wait()  # tpurx: disable=TPURX001 -- wrong rule entirely
        """, rule="TPURX005")
        assert rules_of(fs) == {"TPURX005"}

    def test_malformed_rule_id_is_a_finding(self, tmp_path):
        fs = lint_snippet(tmp_path, "tpu_resiliency/mod.py", """
            x = 1  # tpurx: disable=NOTARULE -- whatever
        """)
        assert "TPURX900" in rules_of(fs)


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------

class TestBaseline:
    def _write_offender(self, tmp_path):
        mod = tmp_path / "tpu_resiliency" / "mod.py"
        mod.parent.mkdir(parents=True, exist_ok=True)
        mod.write_text("def f(ev):\n    ev.wait()\n")
        return mod

    def test_round_trip(self, tmp_path):
        self._write_offender(tmp_path)
        result = run_lint(paths=[str(tmp_path)], root=str(tmp_path),
                          use_baseline=False, rule_ids=["TPURX005"])
        assert len(result.findings) == 1

        bpath = str(tmp_path / "baseline.json")
        bl = Baseline.from_findings(result.findings, bpath)
        for e in bl.entries:
            e.justification = "grandfathered: pre-lint wait"
        bl.save()
        reloaded = Baseline.load(bpath)
        assert [e.key() for e in reloaded.entries] == [e.key() for e in bl.entries]
        assert not reloaded.unjustified()

        gated = run_lint(paths=[str(tmp_path)], root=str(tmp_path),
                         baseline_path=bpath, rule_ids=["TPURX005"])
        assert not gated.findings and len(gated.baselined) == 1

    def test_baseline_keys_on_content_not_line_number(self, tmp_path):
        mod = self._write_offender(tmp_path)
        bpath = str(tmp_path / "baseline.json")
        result = run_lint(paths=[str(tmp_path)], root=str(tmp_path),
                          use_baseline=False, rule_ids=["TPURX005"])
        bl = Baseline.from_findings(result.findings, bpath)
        for e in bl.entries:
            e.justification = "grandfathered"
        bl.save()
        # unrelated edit above the offender moves its line number
        mod.write_text("import os\n\n\ndef f(ev):\n    ev.wait()\n")
        gated = run_lint(paths=[str(tmp_path)], root=str(tmp_path),
                         baseline_path=bpath, rule_ids=["TPURX005"])
        assert not gated.findings and len(gated.baselined) == 1
        # but editing the offending line itself resurfaces the finding
        mod.write_text("def f(ev):\n    ev.wait()  # now touched\n")
        gated = run_lint(paths=[str(tmp_path)], root=str(tmp_path),
                         baseline_path=bpath, rule_ids=["TPURX005"])
        assert len(gated.findings) == 1

    def test_unjustified_and_stale_entries_reported(self, tmp_path):
        self._write_offender(tmp_path)
        bpath = str(tmp_path / "baseline.json")
        with open(bpath, "w") as f:
            json.dump({"entries": [
                {"rule": "TPURX005", "path": "tpu_resiliency/mod.py",
                 "symbol": "ev.wait()", "justification": ""},
                {"rule": "TPURX005", "path": "tpu_resiliency/gone.py",
                 "symbol": "ev.wait()", "justification": "was removed"},
            ]}, f)
        result = run_lint(paths=[str(tmp_path)], root=str(tmp_path),
                          baseline_path=bpath)
        assert len(result.unjustified_baseline) == 1
        assert len(result.stale_baseline) == 1


# ---------------------------------------------------------------------------
# the repo gate (tier-1): zero non-baselined findings, fast, clean baseline
# ---------------------------------------------------------------------------

class TestRepoGate:
    @pytest.fixture(scope="class")
    def repo_result(self):
        # the gate lints the linter too (self-check), with the whole-program
        # tier enabled and --jobs auto — exactly what CI runs
        t0 = time.monotonic()
        result = run_lint(root=REPO, jobs="auto")
        result.elapsed = time.monotonic() - t0
        return result

    def test_zero_non_baselined_findings(self, repo_result):
        assert not repo_result.parse_errors, repo_result.parse_errors
        assert not repo_result.findings, "\n".join(
            f"{f.location()}: {f.rule} {f.message}" for f in repo_result.findings)

    def test_baseline_entries_all_justified_and_live(self, repo_result):
        assert not repo_result.unjustified_baseline, [
            e.key() for e in repo_result.unjustified_baseline]
        assert not repo_result.stale_baseline, [
            e.key() for e in repo_result.stale_baseline]

    def test_full_repo_lint_perf_floor(self, repo_result):
        # PR 8's per-file-only run measured 3.8s; the whole-program tier
        # (symbol table + call graph + 3 interprocedural rules) must stay
        # within 2x that with --jobs auto (measured ~6.0s single-core).
        # Bound carries ~2.5x slack for loaded CI hosts.
        assert repo_result.elapsed < 19.0, f"{repo_result.elapsed:.1f}s"

    def test_lints_itself(self, repo_result):
        # self-check: the tpurx_lint package is part of the default gate
        from tpurx_lint.engine import DEFAULT_PATHS
        assert "tpurx_lint" in DEFAULT_PATHS

    def test_cli_json_output(self):
        import subprocess
        import sys
        out = subprocess.run(
            [sys.executable, "-m", "tpurx_lint", "--format=json"],
            cwd=REPO, capture_output=True, text=True, timeout=120,
        )
        assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
        data = json.loads(out.stdout)
        assert data["ok"] is True
        assert data["findings"] == []


# ---------------------------------------------------------------------------
# SARIF output
# ---------------------------------------------------------------------------

# The structural subset of the SARIF 2.1.0 schema that CI annotators rely
# on: required top-level fields, driver rules with ids, results with ruleId/
# message/locations/regions.  (The full OASIS schema is ~500KB; this captures
# every property the spec marks `required` on the objects we emit.)
SARIF_21_SUBSET_SCHEMA = {
    "type": "object",
    "required": ["version", "runs"],
    "properties": {
        "version": {"const": "2.1.0"},
        "$schema": {"type": "string"},
        "runs": {
            "type": "array", "minItems": 1,
            "items": {
                "type": "object",
                "required": ["tool", "results"],
                "properties": {
                    "tool": {
                        "type": "object", "required": ["driver"],
                        "properties": {"driver": {
                            "type": "object", "required": ["name"],
                            "properties": {
                                "name": {"type": "string"},
                                "rules": {"type": "array", "items": {
                                    "type": "object", "required": ["id"],
                                }},
                            },
                        }},
                    },
                    "results": {"type": "array", "items": {
                        "type": "object",
                        "required": ["message"],
                        "properties": {
                            "ruleId": {"type": "string"},
                            "level": {"enum": ["none", "note", "warning",
                                               "error"]},
                            "message": {"type": "object",
                                        "required": ["text"]},
                            "locations": {"type": "array", "items": {
                                "type": "object",
                                "properties": {"physicalLocation": {
                                    "type": "object",
                                    "properties": {
                                        "artifactLocation": {
                                            "type": "object",
                                            "properties": {"uri": {
                                                "type": "string"}},
                                        },
                                        "region": {
                                            "type": "object",
                                            "properties": {"startLine": {
                                                "type": "integer",
                                                "minimum": 1}},
                                        },
                                    },
                                }},
                            }},
                        },
                    }},
                },
            },
        },
    },
}


class TestSarif:
    def _render(self, tmp_path):
        from tpurx_lint.sarif import render
        mod = tmp_path / "tpu_resiliency" / "mod.py"
        mod.parent.mkdir(parents=True, exist_ok=True)
        mod.write_text("def f(ev):\n    ev.wait()\n")
        result = run_lint(paths=[str(tmp_path)], root=str(tmp_path),
                          use_baseline=False)
        return render(result, all_rules(), str(tmp_path))

    def test_validates_against_sarif_210_schema(self, tmp_path):
        jsonschema = pytest.importorskip("jsonschema")
        log = self._render(tmp_path)
        jsonschema.validate(log, SARIF_21_SUBSET_SCHEMA)
        assert log["version"] == "2.1.0"
        assert "sarif-schema-2.1.0" in log["$schema"]

    def test_findings_carry_stable_fingerprints(self, tmp_path):
        log = self._render(tmp_path)
        results = log["runs"][0]["results"]
        assert any(r["ruleId"] == "TPURX005" for r in results)
        for r in results:
            assert r["partialFingerprints"]["tpurxContentKey/v1"]
        # fingerprint keys on content, not line: re-render after a shift
        mod = tmp_path / "tpu_resiliency" / "mod.py"
        mod.write_text("import os\n\ndef f(ev):\n    ev.wait()\n")
        result2 = run_lint(paths=[str(tmp_path)], root=str(tmp_path),
                           use_baseline=False)
        from tpurx_lint.sarif import render
        log2 = render(result2, all_rules(), str(tmp_path))
        fp = {r["partialFingerprints"]["tpurxContentKey/v1"]
              for r in log["runs"][0]["results"] if r["ruleId"] == "TPURX005"}
        fp2 = {r["partialFingerprints"]["tpurxContentKey/v1"]
               for r in log2["runs"][0]["results"] if r["ruleId"] == "TPURX005"}
        assert fp == fp2

    def test_cli_sarif_output(self):
        import subprocess
        import sys
        out = subprocess.run(
            [sys.executable, "-m", "tpurx_lint", "tpurx_lint/",
             "--format=sarif"],
            cwd=REPO, capture_output=True, text=True, timeout=120,
        )
        assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
        data = json.loads(out.stdout)
        assert data["version"] == "2.1.0"
        assert data["runs"][0]["tool"]["driver"]["name"] == "tpurx-lint"


# ---------------------------------------------------------------------------
# parallel engine
# ---------------------------------------------------------------------------

class TestParallelJobs:
    def test_jobs_equals_serial_findings(self, tmp_path):
        for i in range(6):
            mod = tmp_path / "tpu_resiliency" / f"m{i}.py"
            mod.parent.mkdir(parents=True, exist_ok=True)
            mod.write_text(
                f"def f{i}(ev):\n    ev.wait()\n    print('x')\n")
        serial = run_lint(paths=[str(tmp_path)], root=str(tmp_path),
                          use_baseline=False, jobs=1)
        par = run_lint(paths=[str(tmp_path)], root=str(tmp_path),
                       use_baseline=False, jobs=3)
        key = lambda fs: sorted((f.rule, f.path, f.line) for f in fs)  # noqa: E731
        assert key(par.findings) == key(serial.findings)
        assert len(serial.findings) == 12  # wait + print per module

    def test_suppressions_apply_across_jobs(self, tmp_path):
        mod = tmp_path / "tpu_resiliency" / "m.py"
        mod.parent.mkdir(parents=True, exist_ok=True)
        mod.write_text(
            "def f(ev):\n"
            "    ev.wait()  # tpurx: disable=TPURX005 -- bounded by caller\n")
        par = run_lint(paths=[str(tmp_path)], root=str(tmp_path),
                       use_baseline=False, jobs=2)
        assert not par.findings

    def test_resolve_jobs(self):
        from tpurx_lint.engine import resolve_jobs
        assert resolve_jobs(None) == 1
        assert resolve_jobs(4) == 4
        assert resolve_jobs("auto") >= 1
        assert resolve_jobs(0) >= 1
