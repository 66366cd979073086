"""Engine: discover files, parse each exactly once, run every rule, apply
suppressions, split against the baseline.

Two tiers run over the shared parse products:

- per-file rules (``check_file``) — embarrassingly parallel; ``jobs`` fans
  them out across processes (each worker re-parses only its own slice; the
  parent's parse is reused for everything else);
- the whole-program tier (``finalize``) — runs once in the parent over the
  full ``Project``, with the module-qualified call graph built exactly once
  (``Project.callgraph()``) and shared by every interprocedural rule.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field

from .baseline import Baseline, DEFAULT_BASELINE
from .findings import Finding
from .registry import all_rules
from .source import ParsedFile

_SKIP_DIRS = {"__pycache__", ".git", ".claude", "node_modules", ".venv"}

DEFAULT_PATHS = ["tpu_resiliency", "tests", "tpurx_lint"]


@dataclass
class Project:
    """Everything the rules may look at: parsed files plus the repo root
    (finalize passes read non-Python artifacts like docs through it)."""

    root: str
    files: list = field(default_factory=list)   # list[ParsedFile]
    witness: object = None                      # Witness or None
    witness_pruned: list = field(default_factory=list)
    _cg: object = None

    def file(self, rel: str):
        for pf in self.files:
            if pf.rel == rel:
                return pf
        return None

    def callgraph(self):
        """The whole-program call graph, built once and cached."""
        if self._cg is None:
            from .callgraph import CallGraph
            self._cg = CallGraph.build(self)
        return self._cg

    def read_text(self, rel: str) -> str | None:
        path = os.path.join(self.root, rel)
        try:
            with open(path) as f:
                return f.read()
        except OSError:
            return None


@dataclass
class LintResult:
    findings: list = field(default_factory=list)       # non-baselined
    baselined: list = field(default_factory=list)
    parse_errors: list = field(default_factory=list)   # list[Finding] TPURX999
    stale_baseline: list = field(default_factory=list)
    unjustified_baseline: list = field(default_factory=list)
    witness_pruned: list = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.findings and not self.parse_errors

    def all_raw(self):
        return self.findings + self.baselined


def discover(paths, root: str):
    """Yield (abs, rel) for every .py file under the given paths."""
    seen = set()
    for p in paths:
        ap = p if os.path.isabs(p) else os.path.join(root, p)
        ap = os.path.abspath(ap)
        if os.path.isfile(ap):
            if ap.endswith(".py") and ap not in seen:
                seen.add(ap)
                yield ap, os.path.relpath(ap, root).replace(os.sep, "/")
            continue
        for dirpath, dirnames, filenames in os.walk(ap):
            dirnames[:] = sorted(d for d in dirnames if d not in _SKIP_DIRS)
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                f = os.path.join(dirpath, fn)
                if f in seen:
                    continue
                seen.add(f)
                yield f, os.path.relpath(f, root).replace(os.sep, "/")


def parse_project(paths, root: str) -> tuple:
    project = Project(root=os.path.abspath(root))
    errors = []
    for path, rel in discover(paths, root):
        try:
            with open(path, encoding="utf-8") as f:
                text = f.read()
            project.files.append(ParsedFile.parse(path, rel, text))
        except (SyntaxError, UnicodeDecodeError, OSError) as e:
            errors.append(Finding(
                rule="TPURX999", path=rel,
                line=getattr(e, "lineno", None) or 1,
                message=f"unparseable: {e}"))
    return project, errors


def resolve_jobs(jobs) -> int:
    """Normalize the jobs knob: None/1 -> serial; 'auto'/0 -> cpu count."""
    if jobs in ("auto", 0):
        return max(1, os.cpu_count() or 1)
    if jobs is None:
        return 1
    return max(1, int(jobs))


def _worker_check_files(args):
    """Pool worker: re-parse a slice of files, run per-file rules only.

    Receives (rel, text) pairs — texts were already read by the parent, so
    workers never touch the filesystem; directive findings and suppression
    application stay in the parent (which has its own parse of everything).
    """
    batch, rule_ids = args
    rules = all_rules()
    if rule_ids:
        wanted = set(rule_ids)
        rules = [r for r in rules if r.rule_id in wanted]
    out = []
    for rel, text in batch:
        try:
            pf = ParsedFile.parse(rel, rel, text)
        except (SyntaxError, ValueError):
            continue   # parent already reported TPURX999
        for rule in rules:
            if rule.applies_to(rel):
                out.extend(rule.check_file(pf))
    return out


def _run_per_file_parallel(project, rules, rule_ids, jobs: int) -> list:
    import multiprocessing

    batches = [[] for _ in range(jobs)]
    for i, pf in enumerate(project.files):
        batches[i % jobs].append((pf.rel, pf.text))
    ctx = multiprocessing.get_context("fork")
    with ctx.Pool(processes=jobs) as pool:
        parts = pool.map(
            _worker_check_files,
            [(batch, rule_ids) for batch in batches if batch])
    raw = []
    for part in parts:
        raw.extend(part)
    return raw


def run_lint(paths=None, root=None, baseline_path=None,
             use_baseline: bool = True, rule_ids=None,
             jobs=None, witness_path=None) -> LintResult:
    """Run every (or the selected) rule over `paths` relative to `root`.

    Suppression directives are applied first (their misuse surfaces as
    TPURX900), then the baseline splits what's left into new vs
    grandfathered.  ``jobs`` fans the per-file tier across processes
    ('auto'/0 = cpu count); the whole-program tier always runs once in the
    parent.  ``witness_path`` feeds a runtime sanitizer witness (or a list
    of them) to the lock-order rule for confirm/prune verdicts.
    """
    root = os.path.abspath(root or os.getcwd())
    paths = list(paths) if paths else list(DEFAULT_PATHS)
    project, parse_errors = parse_project(paths, root)

    if witness_path:
        from .witness import Witness
        project.witness = Witness.load(witness_path, root)

    rules = all_rules()
    if rule_ids:
        wanted = set(rule_ids)
        rules = [r for r in rules if r.rule_id in wanted]

    njobs = resolve_jobs(jobs)
    raw = []
    for pf in project.files:
        raw.extend(pf.directive_findings)
    if njobs > 1 and len(project.files) > 1:
        raw.extend(_run_per_file_parallel(project, rules, rule_ids, njobs))
    else:
        for pf in project.files:
            for rule in rules:
                if rule.applies_to(pf.rel):
                    raw.extend(rule.check_file(pf))
    for rule in rules:
        raw.extend(rule.finalize(project))

    kept = []
    for f in raw:
        pf = project.file(f.path)
        if (pf is not None and f.rule != "TPURX900"
                and pf.is_suppressed(f.rule, f.line)):
            continue
        kept.append(f)
    kept.sort(key=Finding.sort_key)

    result = LintResult(parse_errors=parse_errors,
                        witness_pruned=list(project.witness_pruned))
    if use_baseline:
        bl = Baseline.load(baseline_path or DEFAULT_BASELINE)
        result.findings, result.baselined = bl.split(kept)
        # stale/justification audits only make sense over a full-rule run,
        # and staleness only for files this run actually re-checked or that
        # are gone entirely (a partial-path run must not condemn entries it
        # never looked at)
        if not rule_ids:
            parsed = {pf.rel for pf in project.files}
            result.stale_baseline = [
                e for e in bl.stale(kept)
                if e.path in parsed
                or not os.path.exists(os.path.join(root, e.path))]
            result.unjustified_baseline = bl.unjustified()
    else:
        result.findings = kept
    return result
