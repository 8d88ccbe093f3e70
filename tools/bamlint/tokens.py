"""Pass 2 — IOToken lifecycle (linear-type discipline) + pin pairing.

An :class:`IOToken` must flow from its ``submit`` to **exactly one**
``wait``: a dropped token leaks the cache pins taken at submit (refcounts
never return to zero, the lines become unevictable); a double-waited token
over-releases them (refcount underflow corrupts the clock sweep).  The
same linearity governs ``acquire``/``release`` pin pairs inside the state
machinery itself.

The analysis is per-function and deliberately conservative: a token that
*escapes* (returned, yielded, appended to a container, stored into a
structure, or passed to another function) is treated as consumed — its
lifecycle continues in the consumer.  Findings therefore mean "this
binding provably never flows anywhere" (leak) or "this binding is waited
twice on one path" (double wait).

Rules
-----
BAM201  token leak: a ``submit`` result bound to a name
        that is never waited, returned, stored, or passed on.
BAM202  double wait: the same token binding waited more than once on a
        single path (including once-per-iteration waits on a token bound
        outside the loop).
BAM203  unpaired pin: a function that calls ``acquire`` (taking cache
        pins) without releasing them, returning them, or binding them
        into an :class:`IOToken` for the waiter to release.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from tools.bamlint.core import Finding, ModuleInfo
from tools.bamlint.reach import FuncNode, dotted, tail

RULES = {
    "BAM201": "IOToken leaked: submit result never waited or passed on",
    "BAM202": "IOToken waited more than once on a single path",
    "BAM203": "cache pins acquired without release / IOToken hand-off",
}

SUBMIT_TAILS = ("submit",)
WAIT_TAILS = ("wait",)


def _functions(tree: ast.Module):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _own_statements(fn) -> List[ast.stmt]:
    return list(fn.body)


def _walk_own(fn):
    """All nodes of ``fn`` excluding nested function bodies."""
    stack: List[ast.AST] = list(fn.body)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, FuncNode):
            continue               # nested def/lambda: don't descend
        for child in ast.iter_child_nodes(node):
            if isinstance(child, FuncNode):
                continue
            stack.append(child)


def _call_tail(call: ast.Call) -> str:
    return tail(dotted(call.func))


def _is_submit_call(call: ast.Call, aliases: Set[str]) -> bool:
    t = _call_tail(call)
    if t in SUBMIT_TAILS or t.startswith("submit") or \
            t.endswith("_submit"):
        return True
    if isinstance(call.func, ast.Name) and call.func.id in aliases:
        return True
    # submit_jit()(...) inline
    if isinstance(call.func, ast.Call) and \
            _call_tail(call.func).endswith("submit_jit"):
        return True
    return False


def _is_wait_call(call: ast.Call, aliases: Set[str]) -> bool:
    t = _call_tail(call)
    if t in WAIT_TAILS or t.endswith("_wait") or t.startswith("wait"):
        return True
    if isinstance(call.func, ast.Name) and call.func.id in aliases:
        return True
    if isinstance(call.func, ast.Call) and \
            _call_tail(call.func).endswith("wait_jit"):
        return True
    return False


def _source_aliases(fn, needle: str) -> Set[str]:
    """Local names bound to a submit/wait callable (``submit =
    jax.jit(lambda ...: arr.submit(...))``, ``wait = arr.wait_jit()``)."""
    out: Set[str] = set()
    for node in _walk_own(fn):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name):
            try:
                rhs = ast.unparse(node.value)
            except Exception:
                continue
            if needle in rhs:
                out.add(node.targets[0].id)
    return out


class _Event:
    __slots__ = ("kind", "node", "loops", "branch")

    def __init__(self, kind: str, node: ast.AST,
                 loops: Tuple[ast.AST, ...], branch: Tuple[ast.AST, ...]):
        self.kind = kind          # "bind" | "rebind" | "wait" | "escape"
        self.node = node
        self.loops = loops        # enclosing loop nodes, outermost first
        self.branch = branch      # (If-node, "body"/"orelse") chain


def _collect_events(fn, name: str, submit_aliases: Set[str],
                    wait_aliases: Set[str]) -> List[_Event]:
    """Linear (source-ordered) bind/use events for one local name."""
    events: List[_Event] = []

    def rec(stmts, loops, branch):
        for stmt in stmts:
            _stmt_events(stmt, loops, branch)

    def _expr_uses(expr, loops, branch, in_wait_call=False):
        """Register Load-uses of `name` inside an expression."""
        for node in ast.walk(expr):
            if isinstance(node, FuncNode):
                continue
            if isinstance(node, ast.Call):
                is_wait = _is_wait_call(node, wait_aliases)
                for sub in list(node.args) + \
                        [kw.value for kw in node.keywords]:
                    for n2 in ast.walk(sub):
                        if isinstance(n2, ast.Name) and n2.id == name and \
                                isinstance(n2.ctx, ast.Load):
                            events.append(_Event(
                                "wait" if is_wait else "escape",
                                node, loops, branch))
        # bare loads outside calls (return tok, tuples, comparisons...)
        for node in ast.walk(expr):
            if isinstance(node, ast.Name) and node.id == name and \
                    isinstance(node.ctx, ast.Load):
                if not any(node in ast.walk(c) for c in _calls_in(expr)):
                    events.append(_Event("escape", node, loops, branch))

    def _calls_in(expr):
        return [n for n in ast.walk(expr) if isinstance(n, ast.Call)]

    def _binds_name(target) -> Optional[str]:
        if isinstance(target, ast.Name) and target.id == name:
            return "plain"
        if isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                if _binds_name(elt):
                    return "plain"
        return None

    def _stmt_events(stmt, loops, branch):
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return
        if isinstance(stmt, ast.Assign):
            _expr_uses(stmt.value, loops, branch)
            for tgt in stmt.targets:
                if _binds_name(tgt):
                    is_token = isinstance(stmt.value, ast.Call) and \
                        _is_submit_call(stmt.value, submit_aliases) and \
                        isinstance(tgt, (ast.Tuple, ast.List))
                    events.append(_Event(
                        "bind" if is_token else "rebind",
                        stmt, loops, branch))
        elif isinstance(stmt, ast.AugAssign):
            _expr_uses(stmt.value, loops, branch)
        elif isinstance(stmt, ast.Expr):
            _expr_uses(stmt.value, loops, branch)
        elif isinstance(stmt, ast.Return):
            if stmt.value is not None:
                _expr_uses(stmt.value, loops, branch)
        elif isinstance(stmt, (ast.For, ast.AsyncFor)):
            _expr_uses(stmt.iter, loops, branch)
            if _binds_name(stmt.target):
                events.append(_Event("rebind", stmt, loops, branch))
            rec(stmt.body, loops + (stmt,), branch)
            rec(stmt.orelse, loops, branch)
        elif isinstance(stmt, ast.While):
            _expr_uses(stmt.test, loops, branch)
            rec(stmt.body, loops + (stmt,), branch)
            rec(stmt.orelse, loops, branch)
        elif isinstance(stmt, ast.If):
            _expr_uses(stmt.test, loops, branch)
            rec(stmt.body, loops, branch + ((stmt, "body"),))
            rec(stmt.orelse, loops, branch + ((stmt, "orelse"),))
        elif isinstance(stmt, ast.With):
            for item in stmt.items:
                _expr_uses(item.context_expr, loops, branch)
            rec(stmt.body, loops, branch)
        elif isinstance(stmt, ast.Try):
            rec(stmt.body, loops, branch)
            for h in stmt.handlers:
                rec(h.body, loops, branch)
            rec(stmt.orelse, loops, branch)
            rec(stmt.finalbody, loops, branch)
        else:
            for child in ast.iter_child_nodes(stmt):
                if isinstance(child, ast.expr):
                    _expr_uses(child, loops, branch)

    rec(_own_statements(fn), (), ())
    # Within one statement the RHS is evaluated before the target binds
    # (`st, tok = step(st, tok)`), so uses order before (re)binds on the
    # same line.
    events.sort(key=lambda e: (getattr(e.node, "lineno", 0),
                               0 if e.kind in ("wait", "escape") else 1,
                               getattr(e.node, "col_offset", 0)))
    return events


def _sibling_branches(a: _Event, b: _Event) -> bool:
    """True when a and b live in mutually exclusive branches of one If."""
    for (ifa, sidea) in a.branch:
        for (ifb, sideb) in b.branch:
            if ifa is ifb and sidea != sideb:
                return True
    return False


def check(mod: ModuleInfo) -> List[Finding]:
    out: List[Finding] = []
    for fn in _functions(mod.tree):
        out.extend(_check_tokens(mod, fn))
        out.extend(_check_pins(mod, fn))
    return out


def _token_names(fn, submit_aliases: Set[str]) -> Set[str]:
    """Names bound from the non-state half of a submit tuple unpack."""
    names: Set[str] = set()
    for node in _walk_own(fn):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], (ast.Tuple, ast.List)) and \
                isinstance(node.value, ast.Call) and \
                _is_submit_call(node.value, submit_aliases):
            elts = node.targets[0].elts
            # (state, token) or (state, token, extra...) convention
            if len(elts) >= 2 and isinstance(elts[1], ast.Name):
                names.add(elts[1].id)
    return names


def _check_tokens(mod: ModuleInfo, fn) -> List[Finding]:
    out: List[Finding] = []
    submit_aliases = _source_aliases(fn, "submit")
    wait_aliases = _source_aliases(fn, "wait")
    for name in sorted(_token_names(fn, submit_aliases)):
        events = _collect_events(fn, name, submit_aliases, wait_aliases)
        n = len(events)
        for i, ev in enumerate(events):
            if ev.kind != "bind":
                continue
            # uses attributable to this binding: everything up to the next
            # (re)bind — plus, for a binding inside a loop, earlier events
            # in the same loop body (the back edge), unless an earlier
            # (re)bind in that loop body intercepts them.
            uses: List[_Event] = []
            for j in range(i + 1, n):
                if events[j].kind in ("bind", "rebind"):
                    break
                uses.append(events[j])
            else:
                j = n
            if ev.loops:
                loop = ev.loops[-1]
                back = [e for e in events[:i]
                        if loop in e.loops and
                        e.kind not in ("bind", "rebind")]
                intercepted = any(e.kind in ("bind", "rebind")
                                  for e in events[:i] if loop in e.loops)
                if not intercepted:
                    uses.extend(back)
            if not uses:
                out.append(mod.finding(
                    "BAM201", ev.node,
                    f"token `{name}` from this submit is never waited, "
                    "returned, or passed on — its cache pins leak "
                    "(refcounts never return to zero)"))
                continue
            waits = [u for u in uses if u.kind == "wait"]
            # once-per-iteration wait on a token bound outside the loop
            for w in waits:
                if len(w.loops) > len(ev.loops) and \
                        not any(e.kind in ("bind", "rebind")
                                and w.loops[-1] in e.loops
                                for e in events):
                    out.append(mod.finding(
                        "BAM202", w.node,
                        f"token `{name}` is waited inside a loop but "
                        "bound outside it: every iteration after the "
                        "first re-waits the same token and over-releases "
                        "its pins"))
                    break
            else:
                # two waits on one path (not in sibling if/else branches)
                for a in range(len(waits)):
                    for b in range(a + 1, len(waits)):
                        if not _sibling_branches(waits[a], waits[b]):
                            out.append(mod.finding(
                                "BAM202", waits[b].node,
                                f"token `{name}` is waited twice on one "
                                "path — the second wait over-releases "
                                "its cache pins"))
                            break
                    else:
                        continue
                    break
    return out


def _check_pins(mod: ModuleInfo, fn) -> List[Finding]:
    acquires: List[ast.Call] = []
    releases = 0
    returns_acquire = False
    builds_token = False
    for node in _walk_own(fn):
        if isinstance(node, ast.Call):
            t = _call_tail(node)
            if t == "acquire":
                acquires.append(node)
            elif t in ("release", "unpin"):
                releases += 1
            elif t == "IOToken" or t.endswith("Token"):
                builds_token = True
        if isinstance(node, ast.Return) and node.value is not None:
            for sub in ast.walk(node.value):
                if isinstance(sub, ast.Call) and \
                        _call_tail(sub) == "acquire":
                    returns_acquire = True
    if acquires and not (releases or builds_token or returns_acquire):
        return [mod.finding(
            "BAM203", acquires[0],
            "`acquire` takes cache pins but this function neither "
            "releases them, returns the acquired state directly, nor "
            "binds them into an IOToken for the waiter — unpaired pins "
            "make the lines permanently unevictable")]
    return []
