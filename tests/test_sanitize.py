"""Runtime lock-order sanitizer (utils/sanitize.py).

Every test runs the sanitizer in a SUBPROCESS: install() patches
``threading.Lock``/``threading.RLock`` process-globally, which must never
leak into the test runner.  The integration tests close the static<->runtime
loop: the same fixture module is linted (TPURX011, PLAUSIBLE) and executed
under the sanitizer, and the produced witness promotes the finding to
CONFIRMED — or prunes it when the runtime only ever saw one order.
"""

import json
import os
import subprocess
import sys
import textwrap

from tpu_resiliency.utils.env import force_cpu_env
from tpurx_lint import run_lint

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

# decl lines 6 and 7: the lock table keys witness edges by creation site
FIXTURE = """\
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
"""


def run_py(script, timeout=60):
    env = force_cpu_env(dict(os.environ))
    env.pop("TPURX_SANITIZE", None)
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, timeout=timeout,
        cwd=REPO, env=env,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return proc.stdout


class TestSanitizerBehavior:
    def test_inversion_trips_and_is_witnessed(self, tmp_path):
        wit = tmp_path / "w.jsonl"
        out = run_py(f"""
            import threading
            from tpu_resiliency.utils import sanitize
            sanitize.install(witness_path={str(wit)!r})
            a = threading.Lock()
            b = threading.Lock()
            with a:
                with b:
                    pass
            try:
                with b:
                    with a:
                        pass
                print("NOTRIP")
            except sanitize.LockOrderViolation:
                print("TRIP")
            sanitize.close_witness()
        """)
        assert "TRIP" in out
        recs = [json.loads(l) for l in wit.read_text().splitlines()]
        events = [r["event"] for r in recs]
        assert "meta" in events and "edge" in events and "cycle" in events
        cyc = next(r for r in recs if r["event"] == "cycle")
        assert cyc["kind"] == "order" and len(cyc["chain"]) >= 2

    def test_rlock_reentrancy_and_condition_wait_clean(self, tmp_path):
        wit = tmp_path / "w.jsonl"
        run_py(f"""
            import threading, time
            from tpu_resiliency.utils import sanitize
            sanitize.install(witness_path={str(wit)!r})
            r = threading.RLock()
            with r:
                with r:
                    pass
            cv = threading.Condition()
            hit = []
            def waiter():
                with cv:
                    cv.wait(timeout=5)
                    hit.append(1)
            t = threading.Thread(target=waiter, daemon=True)
            t.start()
            time.sleep(0.2)
            with cv:
                cv.notify_all()
            t.join(timeout=5)
            assert hit, "condition wait/notify must work through the wrapper"
            ev = threading.Event(); ev.set(); assert ev.is_set()
            import queue
            q = queue.Queue(); q.put(1); assert q.get(timeout=1) == 1
            assert sanitize.stats()["cycles"] == 0
            sanitize.close_witness()
        """)
        recs = [json.loads(l) for l in wit.read_text().splitlines()]
        assert not [r for r in recs if r["event"] == "cycle"]

    def test_lock_self_reacquire_trips(self, tmp_path):
        out = run_py("""
            import threading
            from tpu_resiliency.utils import sanitize
            sanitize.install()
            mu = threading.Lock()
            try:
                with mu:
                    mu.acquire()
                print("NOTRIP")
            except sanitize.LockOrderViolation as e:
                assert "self-deadlock" in str(e)
                print("TRIP")
        """)
        assert "TRIP" in out

    def test_install_from_env_via_package_import(self, tmp_path):
        wit = tmp_path / "w.jsonl"
        env = force_cpu_env(dict(os.environ))
        env["TPURX_SANITIZE"] = "1"
        env["TPURX_SANITIZE_WITNESS_PATH"] = str(tmp_path / "w.%r.jsonl")
        proc = subprocess.run(
            [sys.executable, "-c",
             "import tpu_resiliency\n"
             "from tpu_resiliency.utils import sanitize\n"
             "assert sanitize.stats()['installed']\n"
             "import threading\n"
             "a = threading.Lock()\n"
             "with a: pass\n"
             "print('path', sanitize.stats()['witness_path'])\n"],
            capture_output=True, text=True, timeout=60, cwd=REPO, env=env,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        # %r expanded to the (default 0) rank
        assert str(tmp_path / "w.0.jsonl") in proc.stdout
        assert (tmp_path / "w.0.jsonl").exists()
        del wit


class TestWitnessFeedbackLoop:
    def _fixture(self, tmp_path):
        mod = tmp_path / "tpu_resiliency" / "m.py"
        mod.parent.mkdir(parents=True, exist_ok=True)
        mod.write_text(FIXTURE)
        return mod

    def _run_fixture(self, tmp_path, mod, wit, body):
        run_py(f"""
            from tpu_resiliency.utils import sanitize
            sanitize.install(witness_path={str(wit)!r})
            src = open({str(mod)!r}).read()
            ns = {{}}
            exec(compile(src, {str(mod)!r}, "exec"), ns)
            c = ns["C"]()
            {body}
            sanitize.close_witness()
        """)

    def test_sanitizer_witness_confirms_static_cycle(self, tmp_path):
        mod = self._fixture(tmp_path)
        static = run_lint(paths=[str(tmp_path)], root=str(tmp_path),
                          use_baseline=False, rule_ids=["TPURX011"])
        assert len(static.findings) == 1
        assert "[PLAUSIBLE]" in static.findings[0].message

        wit = tmp_path / "w.jsonl"
        self._run_fixture(tmp_path, mod, wit, """
            c.one()
            try:
                c.two()
            except sanitize.LockOrderViolation:
                pass  # expected: the sanitizer trips on the inversion
        """)
        confirmed = run_lint(paths=[str(tmp_path)], root=str(tmp_path),
                             use_baseline=False, rule_ids=["TPURX011"],
                             witness_path=str(wit))
        assert len(confirmed.findings) == 1
        assert "[CONFIRMED]" in confirmed.findings[0].message

    def test_sanitizer_witness_prunes_one_sided_order(self, tmp_path):
        mod = self._fixture(tmp_path)
        wit = tmp_path / "w.jsonl"
        self._run_fixture(tmp_path, mod, wit, "c.one()")
        pruned = run_lint(paths=[str(tmp_path)], root=str(tmp_path),
                          use_baseline=False, rule_ids=["TPURX011"],
                          witness_path=str(wit))
        assert not pruned.findings
        assert len(pruned.witness_pruned) == 1
        assert "[PRUNED]" in pruned.witness_pruned[0].message
