"""Shared detection of unbounded blocking calls.

Used by TPURX005 (deadline discipline everywhere) and TPURX006 (abort-path
safety), so both rules agree on what "blocks without a deadline" means.

The contract is intentionally about INTENT, not value: any non-None timeout
expression counts as bounded — the rule enforces that someone chose a bound,
not what the bound is.
"""

from __future__ import annotations

import ast

from .astutil import attr_chain, call_name, has_finite_timeout, keyword, is_none_constant

# attribute-call names that park the caller until an external event
_WAIT_ATTRS = {"wait", "wait_stale", "watch_stale"}

_SUBPROCESS_FUNCS = {
    "subprocess.run", "subprocess.check_output", "subprocess.check_call",
    "subprocess.call",
}

# raw byte-wait receivers: a C-level wait no async raise can interrupt
_RECV_ATTRS = {"recv", "recv_into", "recvfrom", "recvfrom_into", "recvmsg"}

# The sanctioned interruptible I/O core: the ONLY module allowed to touch
# raw socket recv/send waits directly.  Every wait there is sliced at the
# TPURX_STORE_POLL_S quantum inside a Python-level loop, which is the whole
# point — everyone else must either bound the socket (settimeout/poll in
# the same function) or go through the store client.
SANCTIONED_SOCKET_CORE = (
    "tpu_resiliency/store/client.py",
)


def _receiver_hints_queue(func: ast.Attribute) -> bool:
    chain = attr_chain(func.value).lower()
    last = chain.rsplit(".", 1)[-1]
    return "queue" in last or last == "q" or last.endswith("_q")


def _receiver_hints_socket(func: ast.Attribute) -> bool:
    chain = attr_chain(func.value).lower()
    last = chain.rsplit(".", 1)[-1]
    return "sock" in last or "conn" in last


def _enclosing_function(pf, node):
    cur = pf.parent(node)
    while cur is not None and not isinstance(
        cur, (ast.FunctionDef, ast.AsyncFunctionDef)
    ):
        cur = pf.parent(cur)
    return cur


def _function_bounds_socket(pf, node) -> bool:
    """True when the enclosing function shows deadline intent for its
    socket/pipe reads: a finite ``settimeout(...)``, a finite ``poll(...)``
    gate (the multiprocessing.Connection idiom), or a ``.poll`` handed to
    ``run_in_executor`` with a timeout operand.  Intent, not value — the
    rule enforces that someone chose a bound, not what the bound is."""
    fn = _enclosing_function(pf, node)
    if fn is None:
        return False
    for sub in ast.walk(fn):
        if not isinstance(sub, ast.Call):
            continue
        if isinstance(sub.func, ast.Attribute):
            if (sub.func.attr == "settimeout" and sub.args
                    and not is_none_constant(sub.args[0])):
                return True
            if sub.func.attr == "poll":
                kw = keyword(sub, "timeout")
                if (sub.args and not is_none_constant(sub.args[0])) or (
                    kw is not None and not is_none_constant(kw)
                ):
                    return True
            if sub.func.attr == "run_in_executor" and any(
                isinstance(a, ast.Attribute) and a.attr == "poll"
                for a in sub.args
            ):
                return True
    return False


def _inside_asyncio_wait_for(pf, node) -> bool:
    parent = pf.parent(node)
    # unwrap `await x.wait()` one level
    if isinstance(parent, ast.Await):
        parent = pf.parent(parent)
    return (
        isinstance(parent, ast.Call)
        and call_name(parent) in ("asyncio.wait_for", "wait_for")
        and node in ast.walk(parent)
    )


def unbounded_blocking_calls(pf, scope_node=None):
    """Yield (call_node, description) for every unbounded blocking call.

    ``scope_node`` limits the walk (used by the abort-path rule to scan one
    reachable function); default is the whole module.
    """
    root = scope_node if scope_node is not None else pf.tree
    for node in ast.walk(root):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        dotted = call_name(node)

        if isinstance(func, ast.Attribute):
            attr = func.attr
            if attr in _WAIT_ATTRS:
                if _inside_asyncio_wait_for(pf, node):
                    continue
                if not has_finite_timeout(node):
                    yield node, (
                        f".{attr}() without a finite timeout (event/condition/"
                        f"process wait can park forever — pass timeout=)"
                    )
                continue
            if attr == "join" and not node.args and not node.keywords:
                # zero-arg .join() can't be str.join (that needs an iterable)
                yield node, (
                    ".join() without a timeout (a wedged thread/process "
                    "parks the joiner forever — pass a bound)"
                )
                continue
            if attr == "join" and (node.args or node.keywords):
                # thread/process join with explicit timeout=None
                kw = keyword(node, "timeout")
                if kw is not None and is_none_constant(kw):
                    yield node, ".join(timeout=None) is unbounded"
                elif (not node.keywords and len(node.args) == 1
                      and is_none_constant(node.args[0])):
                    yield node, ".join(None) is unbounded"
                continue
            if attr == "communicate" and not has_finite_timeout(node):
                yield node, (
                    ".communicate() without timeout= blocks until the child "
                    "exits"
                )
                continue
            if attr == "result" and not node.args and keyword(node, "timeout") is None:
                yield node, (
                    ".result() without timeout= parks on the future forever"
                )
                continue
            if attr == "settimeout" and node.args and is_none_constant(node.args[0]):
                yield node, "settimeout(None) makes the socket blocking-forever"
                continue
            if attr in _RECV_ATTRS and _receiver_hints_socket(func):
                if pf.rel in SANCTIONED_SOCKET_CORE:
                    continue  # the quantum-sliced I/O core itself
                # positional args to recv-family calls are byte counts,
                # never timeouts — only a timeout= keyword bounds them
                if has_finite_timeout(node, positional_ok=False):
                    continue  # exchange.recv(..., timeout=t) style wrappers
                if _function_bounds_socket(pf, node):
                    continue
                yield node, (
                    f"raw .{attr}() with no deadline in scope (no finite "
                    f"settimeout/poll in the enclosing function): an "
                    f"unbounded C-level socket wait blocks async raises — "
                    f"bound it or route through the store client's "
                    f"interruptible I/O core"
                )
                continue
            if (attr == "get" and not node.args
                    and keyword(node, "timeout") is None
                    and _receiver_hints_queue(func)):
                yield node, (
                    "queue .get() without timeout= blocks forever if the "
                    "producer dies"
                )
                continue

        if dotted in _SUBPROCESS_FUNCS and keyword(node, "timeout") is None:
            yield node, f"{dotted}() without timeout= can hang on the child"
            continue
        if dotted in ("socket.create_connection",) and len(node.args) < 2 \
                and keyword(node, "timeout") is None:
            yield node, (
                "socket.create_connection without timeout= inherits the "
                "global default (None)"
            )
            continue
        if dotted in ("select.select",) and len(node.args) == 3:
            yield node, "select.select without a timeout blocks forever"
