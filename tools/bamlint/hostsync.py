"""Pass 1 — host-sync / retrace hazards inside jit-reachable code.

These are the exact patterns behind the submit/wait control-path overhead
the hot-path benchmark tracks (``benchmarks/hot_path.py``): a hidden host sync
serializes the submission window; a shape-dependent Python branch or a
per-call ``jax.jit`` wrapper forces a retrace/recompile on every op.

Rules
-----
BAM101  ``.block_until_ready()`` inside jit-reachable code — a host sync
        on the request path.
BAM102  host transfer of a traced value inside jit-reachable code:
        ``.item()`` / ``.tolist()``, or ``float()``/``int()``/``bool()``/
        ``np.asarray()``/``np.array()`` applied to a tracer-derived value.
BAM103  ``jax.debug.print`` / ``pl.debug_print`` / ``print`` inside a
        Pallas kernel body.
BAM104  Python ``if``/``while``/``for`` control flow conditioned on a
        tracer-derived value inside jit-reachable code (forces a retrace
        per distinct value, or a ConcretizationError).
BAM105  ``jax.jit(...)`` created inside a function body: a fresh wrapper
        per call defeats the compilation cache — hoist it to module level,
        bind it to ``self.<attr>`` once, or use the instance's jit-cached
        op family (``read_jit``/``submit_jit``/``wait_jit``).
"""
from __future__ import annotations

import ast
from typing import List

from tools.bamlint.core import Finding, ModuleInfo
from tools.bamlint.reach import (
    FuncNode, ModuleAnalysis, TaintTracker, dotted, tail,
)

RULES = {
    "BAM101": "host sync (.block_until_ready) inside jit-reachable code",
    "BAM102": "host transfer of a traced value inside jit-reachable code",
    "BAM103": "debug print inside a Pallas kernel",
    "BAM104": "Python control flow on a traced value inside jit-reachable "
              "code",
    "BAM105": "per-call jax.jit wrapper defeats the compilation cache",
}

HOST_CAST_FNS = {"float", "int", "bool"}
NP_TRANSFER = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}


def check(mod: ModuleInfo) -> List[Finding]:
    analysis = ModuleAnalysis(mod.tree)
    out: List[Finding] = []

    # BAM105 applies to every function body, traced or host-side: the
    # per-call wrapper hazard lives precisely in host driver loops.
    for fi in analysis.funcs.values():
        # a nested `@jax.jit def f` re-traces on every call of the
        # enclosing function — same per-call-wrapper hazard.
        if fi.parent is not None:
            for dec in getattr(fi.node, "decorator_list", []):
                is_jit = tail(dotted(dec)) == "jit" or (
                    isinstance(dec, ast.Call)
                    and tail(dotted(dec.func)) == "partial"
                    and any(tail(dotted(a)) == "jit" for a in dec.args))
                if is_jit:
                    out.append(mod.finding(
                        "BAM105", dec,
                        "`@jax.jit` on a function nested inside another "
                        "function: every call of the outer function "
                        "builds a fresh wrapper and recompiles; hoist "
                        "the jitted step to module level or cache it "
                        "per instance"))
        tt = TaintTracker(fi)
        for node in tt.walk_own():
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, (ast.Name, ast.Attribute)) and \
                    tail(dotted(node.func)) == "jit" and \
                    not _is_self_bound_jit(node, fi):
                out.append(mod.finding(
                    "BAM105", node,
                    "`jax.jit` wrapper created inside a function: a "
                    "fresh wrapper per call recompiles at every "
                    "invocation; hoist to module level, bind once to "
                    "`self.<attr>`, or use the instance's *_jit() "
                    "cached op family"))

    for fi in analysis.reachable_functions():
        tt = TaintTracker(fi)
        in_kernel = fi.kernel_reachable
        for node in tt.walk_own():
            if isinstance(node, ast.Call):
                fname = dotted(node.func)
                t = tail(fname)
                if t == "block_until_ready":
                    out.append(mod.finding(
                        "BAM101", node,
                        "host sync `.block_until_ready()` inside "
                        "jit-reachable code serializes the submission "
                        "window; sync at the host call site instead"))
                elif t in ("item", "tolist"):
                    out.append(mod.finding(
                        "BAM102", node,
                        f"`.{t}()` transfers a traced value to the host "
                        "inside jit-reachable code (device round-trip per "
                        "op); keep the value on device or move this to "
                        "the host call site"))
                elif t in HOST_CAST_FNS and isinstance(node.func, ast.Name):
                    if node.args and tt.expr_tainted(node.args[0]):
                        out.append(mod.finding(
                            "BAM102",
                            node,
                            f"`{t}()` of a traced value inside "
                            "jit-reachable code forces a host sync "
                            "(ConcretizationError under jit); use jnp "
                            "ops or hoist to the host call site"))
                elif fname in NP_TRANSFER:
                    if node.args and tt.expr_tainted(node.args[0]):
                        out.append(mod.finding(
                            "BAM102", node,
                            f"`{fname}()` of a traced value inside "
                            "jit-reachable code is a device->host "
                            "transfer; use jnp.asarray or hoist"))
                elif in_kernel and (
                        fname in ("jax.debug.print", "debug.print")
                        or t == "debug_print"
                        or (t == "print"
                            and isinstance(node.func, ast.Name))):
                    out.append(mod.finding(
                        "BAM103", node,
                        "debug print inside a Pallas kernel body — "
                        "serializes the kernel and breaks on TPU; strip "
                        "it before it reaches the hot path"))
            elif isinstance(node, ast.If) or isinstance(node, ast.While):
                if tt.expr_tainted(node.test):
                    out.append(mod.finding(
                        "BAM104", node,
                        "Python `if`/`while` on a traced value inside "
                        "jit-reachable code — retraces per value or "
                        "raises under jit; use jnp.where / lax.cond"))
            elif isinstance(node, ast.For):
                if tt.expr_tainted(node.iter) and \
                        not _is_container_iteration(node):
                    out.append(mod.finding(
                        "BAM104", node,
                        "Python `for` over a traced value inside "
                        "jit-reachable code — unrolls/retraces per "
                        "shape; use lax.scan / lax.fori_loop"))
    return out


def _is_container_iteration(node: ast.For) -> bool:
    """True for pytree-container loops that are static under jit despite a
    tainted iterable: dict-key iteration (``for k in aux: aux[k] ...``),
    iteration over a subscripted container (``cache["layers"]``), and
    ``enumerate``/``zip``/``reversed`` over such shapes.  Loop count is a
    trace-time constant in all of these — not a retrace hazard."""
    iters: List[ast.expr] = [node.iter]
    it = node.iter
    if isinstance(it, ast.Call) and isinstance(it.func, ast.Name) and \
            it.func.id in ("enumerate", "zip", "reversed"):
        iters = list(it.args)
    for e in iters:
        if isinstance(e, ast.Subscript):
            continue
        if isinstance(e, ast.Name):
            # dict-key idiom: the target indexes back into the iterable
            tgt_names = {n.id for n in ast.walk(node.target)
                         if isinstance(n, ast.Name)}
            keyed = any(
                isinstance(s, ast.Subscript) and
                isinstance(s.value, ast.Name) and s.value.id == e.id and
                isinstance(s.slice, ast.Name) and s.slice.id in tgt_names
                for b in node.body for s in ast.walk(b))
            if keyed:
                continue
        return False
    return True


def _is_self_bound_jit(call: ast.Call, fi) -> bool:
    """True when the jit result is cached on the instance
    (``self.x = jax.jit(...)``) — a once-per-object wrapper, not
    per-call."""
    node = fi.node
    for stmt in ast.walk(node):
        if isinstance(stmt, ast.Assign) and stmt.value is call:
            for tgt in stmt.targets:
                if isinstance(tgt, ast.Attribute) and \
                        isinstance(tgt.value, ast.Name) and \
                        tgt.value.id in ("self", "cls"):
                    return True
    return False
