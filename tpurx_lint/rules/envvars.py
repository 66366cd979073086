"""TPURX010: every TPURX_* knob is declared once, typed, defaulted, and
documented — reads go through the utils/env.py registry, and runtime
WRITES go through the policy actuator.

54 knobs accreted over seven PRs, each read site re-deciding its own default
and parse ("!= '0'" here, "== '1'" there).  The registry gives each knob one
name, one type, one default, one doc line; this rule bans literal TPURX_*
environment reads everywhere else and cross-checks the registry against
docs/configuration.md.

The write ban exists because the adaptive policy engine
(tpu_resiliency/policy/) is the single sanctioned author of runtime knob
changes: it goes through ``env.set_runtime_override`` so every change is
typed, journaled, and visible to ``Knob.raw()`` without racing child
process environments.  A stray ``os.environ["TPURX_..."] = ...`` anywhere
else silently fights the controller (the override layer shadows it) and
never reaches the decision journal.  Identity republication — the
launcher stamping ``TPURX_RANK``/``TPURX_WORLD_SIZE`` after a mesh
shrink, the straggler detector publishing its shm name — is exempt via
``WRITE_EXEMPT``: those are facts children must inherit through the real
environment, not resiliency knobs, and ``finalize`` cross-checks that
every exempt key really is identity-group or publisher-documented
("set by ...") in the registry.
"""

from __future__ import annotations

import ast

from ..astutil import attr_chain, call_name
from ..findings import Finding
from ..registry import Rule, register

ENV_MODULE = "tpu_resiliency/utils/env.py"
DOC_PATH = "docs/configuration.md"
POLICY_PREFIX = "tpu_resiliency/policy/"

# Keys legitimately written to the REAL environment outside policy/: rank
# identity republished by the launcher for child inheritance, and
# publisher-owned plumbing whose registry doc declares its writer
# ("set by the ...").  finalize() verifies each entry still qualifies.
WRITE_EXEMPT = (
    "TPURX_RANK",
    "TPURX_LOCAL_RANK",
    "TPURX_WORLD_SIZE",
    "TPURX_OPRING_SHM",
)


def _module_string_consts(tree) -> dict:
    """Module-level NAME = "literal" bindings (the ENV_FOO = "TPURX_FOO"
    idiom) so reads through the constant are still attributed to the knob."""
    out = {}
    for node in tree.body:
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)):
            out[node.targets[0].id] = node.value.value
    return out


def _tpurx_literal_in(expr, consts) -> str:
    """First string (constant or resolved module constant) starting with
    TPURX_ inside the key expression."""
    for sub in ast.walk(expr):
        if (isinstance(sub, ast.Constant) and isinstance(sub.value, str)
                and sub.value.startswith("TPURX_")):
            return sub.value
        if isinstance(sub, ast.Name):
            val = consts.get(sub.id, "")
            if val.startswith("TPURX_"):
                return val
    return ""


def _env_read_key(node: ast.AST, consts) -> str:
    """TPURX key literal when `node` reads the environment, else ''."""
    if isinstance(node, ast.Call):
        dotted = call_name(node)
        if dotted in ("os.getenv", "os.environ.get") and node.args:
            return _tpurx_literal_in(node.args[0], consts)
    if isinstance(node, ast.Subscript) and isinstance(node.ctx, ast.Load):
        if attr_chain(node.value) == "os.environ":
            return _tpurx_literal_in(node.slice, consts)
    if isinstance(node, ast.Compare) and len(node.ops) == 1 \
            and isinstance(node.ops[0], (ast.In, ast.NotIn)):
        if attr_chain(node.comparators[0]) == "os.environ":
            return _tpurx_literal_in(node.left, consts)
    return ""


def _env_write_key(node: ast.AST, consts) -> str:
    """TPURX key literal when `node` MUTATES the environment, else ''."""
    if isinstance(node, ast.Subscript) \
            and isinstance(node.ctx, (ast.Store, ast.Del)):
        if attr_chain(node.value) == "os.environ":
            return _tpurx_literal_in(node.slice, consts)
    if isinstance(node, ast.Call):
        dotted = call_name(node)
        if dotted in ("os.environ.pop", "os.environ.setdefault",
                      "os.putenv") and node.args:
            return _tpurx_literal_in(node.args[0], consts)
        if dotted == "os.environ.update":
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                key = _tpurx_literal_in(arg, consts)
                if key:
                    return key
            for kw in node.keywords:
                if kw.arg and kw.arg.startswith("TPURX_"):
                    return kw.arg
    return ""


def declared_knob_names(env_pf) -> list:
    """(name, lineno) for every Knob("NAME", ...) literal in env.py."""
    out = []
    for node in ast.walk(env_pf.tree):
        if (isinstance(node, ast.Call)
                and call_name(node).split(".")[-1] == "Knob"
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            out.append((node.args[0].value, node.lineno))
    return out


def declared_knob_meta(env_pf) -> dict:
    """name -> (doc, group) for every Knob("NAME", ...) literal in env.py
    (doc is the 4th positional arg, group the keyword; '' when absent)."""
    out = {}
    for node in ast.walk(env_pf.tree):
        if (isinstance(node, ast.Call)
                and call_name(node).split(".")[-1] == "Knob"
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            doc = ""
            if len(node.args) > 3 and isinstance(node.args[3], ast.Constant) \
                    and isinstance(node.args[3].value, str):
                doc = node.args[3].value
            group = ""
            for kw in node.keywords:
                if kw.arg == "group" and isinstance(kw.value, ast.Constant):
                    group = str(kw.value.value)
            out[node.args[0].value] = (doc, group)
    return out


@register
class EnvRegistryRule(Rule):
    rule_id = "TPURX010"
    name = "env-registry"
    rationale = (
        "All TPURX_* environment reads route through the typed registry in "
        "utils/env.py (one declared name/type/default/doc per knob); every "
        "declared knob must be cataloged in docs/configuration.md; runtime "
        "TPURX_* writes are the policy actuator's monopoly "
        "(env.set_runtime_override) — direct os.environ mutation outside "
        "tpu_resiliency/policy/ is banned except for launcher identity "
        "republication (WRITE_EXEMPT)."
    )
    scope = ("tpu_resiliency/",)
    exclude = (ENV_MODULE,)

    def check_file(self, pf):
        consts = _module_string_consts(pf.tree)
        in_policy = pf.rel.startswith(POLICY_PREFIX)
        for node in ast.walk(pf.tree):
            key = _env_read_key(node, consts)
            if key:
                yield pf.finding(
                    self.rule_id, node,
                    f"raw environment read of {key!r} — declare the knob in "
                    f"utils/env.py and read it through the registry",
                )
                continue
            key = _env_write_key(node, consts)
            if key and not in_policy and key not in WRITE_EXEMPT:
                yield pf.finding(
                    self.rule_id, node,
                    f"direct os.environ write of {key!r} — runtime knob "
                    f"changes go through env.set_runtime_override (the "
                    f"policy actuator in tpu_resiliency/policy/ is the "
                    f"sanctioned writer)",
                )

    def finalize(self, project):
        env_pf = project.file(ENV_MODULE)
        if env_pf is None:
            return
        # keep the write-exemption list honest: an exempt key must still be
        # identity-group or carry a publisher doc ("set by the ...") — a
        # repurposed knob loses its exemption here, not silently
        meta = declared_knob_meta(env_pf)
        for key in WRITE_EXEMPT:
            if key not in meta:
                continue  # minimal fixtures need not declare every key
            doc, group = meta[key]
            if group != "identity" and "set by" not in doc:
                yield env_pf.finding(
                    self.rule_id, 1,
                    f"WRITE_EXEMPT key {key} is neither identity-group nor "
                    f"publisher-documented ('set by ...') — it no longer "
                    f"qualifies for direct os.environ writes",
                )
        declared = declared_knob_names(env_pf)
        seen = {}
        for name, lineno in declared:
            if name in seen:
                yield env_pf.finding(
                    self.rule_id, lineno,
                    f"knob {name} declared more than once (first at line "
                    f"{seen[name]})",
                )
            else:
                seen[name] = lineno
        doc = project.read_text(DOC_PATH)
        if doc is None:
            yield Finding(
                rule=self.rule_id, path=DOC_PATH, line=1,
                message=f"{DOC_PATH} is missing — regenerate it with "
                        f"'python -m tpu_resiliency.utils.env --write'",
            )
            return
        for name, lineno in declared:
            if name not in doc:
                yield env_pf.finding(
                    self.rule_id, lineno,
                    f"knob {name} is not documented in {DOC_PATH} — "
                    f"regenerate it with 'python -m tpu_resiliency.utils.env "
                    f"--write'",
                )
