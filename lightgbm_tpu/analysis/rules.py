"""jaxlint rule visitors (JL001-JL005).

Each rule is a small class with a rule id and a ``visit(ctx)`` that walks
the pre-computed :class:`~lightgbm_tpu.analysis.jaxlint.FileContext` and
returns findings. The engine (jaxlint.py) owns jit-scope resolution,
suppression comments and the baseline diff; rules only pattern-match.

The rules encode the classic JAX performance/correctness regressions for
this codebase's hot path (SURVEY L0/L4: the tree-learner compute engine):

JL001  host-sync calls inside jit-traced code (``.item()``, ``float()`` /
       ``int()`` on arrays, ``np.asarray`` on jax values) — each one is a
       device->host round-trip (a pipeline stall) or a tracer
       concretization error.
JL002  Python ``for``/``while``/``if`` over traced values in jitted
       bodies — tracer-leak heuristic (should be ``lax.cond`` /
       ``lax.while_loop`` / ``jnp.where``).
JL003  recompile hazards at jit boundaries: dict/str arguments to a
       jitted callable without static_argnums/static_argnames, and
       ``jax.jit(...)`` created inside a loop (fresh cache every pass).
JL004  dtype-widening literals in kernel files: ``np.float64`` in traced
       code, or float literals fed to jnp constructors without an explicit
       dtype (promote to f64 under jax_enable_x64).
JL005  wall-clock timing around jax dispatch without a completion barrier
       (``block_until_ready`` / device fetch) — measures dispatch, not
       execution — and ``timer.section(...)`` without ``sync=`` (the
       utils/timer.py contract) in dispatching functions.
"""
from __future__ import annotations

import ast
from typing import List


def callee_chain(func: ast.AST) -> str:
    """Dotted name of a call target ("np.asarray", "jax.lax.cond", "float");
    empty string when the target is not a plain name/attribute chain."""
    parts = []
    node = func
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    elif parts:
        # rooted at a call/subscript (e.g. get_timer().section): keep the
        # attribute tail so attr-based rules still see it
        parts.append("")
    return ".".join(reversed(parts))


NUMPY_ALIASES = {"np", "numpy", "onp", "_np"}
TIMING_CALLS = {
    "time.time", "time.perf_counter", "time.monotonic",
    "time.process_time", "timeit.default_timer",
}
# attribute substrings that prove a completion barrier / host fetch
SYNC_ATTRS = ("block_until_ready", "device_get", "_force_sync")
# attrs of a traced array that are static at trace time (not leaks)
STATIC_ARRS = {"shape", "ndim", "dtype", "size", "aval", "weak_type"}


def _is_static_expr(node: ast.AST) -> bool:
    """Expression whose value is static at trace time: `.shape[0]`,
    `x.ndim`, `len(...)` and arithmetic over those."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.Attribute):
        return node.attr in STATIC_ARRS or _is_static_expr(node.value)
    if isinstance(node, ast.Subscript):
        return _is_static_expr(node.value)
    if isinstance(node, ast.BinOp):
        return _is_static_expr(node.left) and _is_static_expr(node.right)
    if isinstance(node, ast.Call):
        return callee_chain(node.func) in ("len", "min", "max") and all(
            _is_static_expr(a) for a in node.args)
    return False


def _wraps_dispatch(node: ast.Call) -> bool:
    """float(jnp.sum(x))-style: the scalar conversion IS the barrier."""
    for sub in ast.walk(node.args[0]) if node.args else ():
        if isinstance(sub, ast.Call):
            root = callee_chain(sub.func).split(".", 1)[0]
            if root in ("jnp", "jax"):
                return True
    return False


class HostSyncRule:
    """JL001: device->host syncs inside jit-traced code."""

    rule = "JL001"

    def visit(self, ctx) -> List:
        out = []
        for fi in ctx.jit_funcs:
            for node in ast.walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                # attribute each call to its innermost function only —
                # nested defs are themselves in jit_funcs, so walking
                # past them here would double-report their hazards
                if ctx.enclosing(node) is not fi:
                    continue
                chain = callee_chain(node.func)
                base, _, attr = chain.rpartition(".")
                if (isinstance(node.func, ast.Attribute) and
                        attr in ("item", "tolist") and not node.args):
                    out.append(ctx.finding(
                        self.rule, node, fi,
                        f"`.{attr}()` forces a device->host sync inside "
                        "jit-traced code"))
                elif (chain in ("float", "int", "bool", "complex") and
                        len(node.args) == 1 and
                        not _is_static_expr(node.args[0])):
                    out.append(ctx.finding(
                        self.rule, node, fi,
                        f"`{chain}()` on an array concretizes the tracer "
                        "(host sync / ConcretizationTypeError) inside "
                        "jit-traced code"))
                elif base in NUMPY_ALIASES and attr in ("asarray", "array"):
                    out.append(ctx.finding(
                        self.rule, node, fi,
                        f"`{base}.{attr}` on a jax value forces a "
                        "device->host transfer inside jit-traced code"))
                elif chain == "jax.device_get":
                    out.append(ctx.finding(
                        self.rule, node, fi,
                        "`jax.device_get` inside jit-traced code forces a "
                        "device->host round-trip"))
        return out


class TracerLeakRule:
    """JL002: Python control flow over (potentially) traced parameters.

    Static config params (``cfg``/``hp``/``backend=...``) branch at trace
    time all over the grower factories — legitimate program
    specialization. The rule therefore only fires on parameters with
    positive ARRAY evidence in the same function: passed to a jnp/lax/jax
    call or subscripted directly.
    """

    rule = "JL002"

    def visit(self, ctx) -> List:
        out = []
        for fi in ctx.jit_funcs:
            if not fi.params:
                continue
            arrayish = self._arrayish_params(fi)
            if not arrayish:
                continue
            for node in ast.walk(fi.node):
                if isinstance(node, (ast.If, ast.While)):
                    expr, kind = node.test, type(node).__name__.lower()
                elif isinstance(node, ast.For):
                    expr, kind = node.iter, "for"
                else:
                    continue
                if ctx.enclosing(node) is not fi:  # innermost scope only
                    continue
                hits = self._traced_names(expr) & arrayish
                if hits:
                    out.append(ctx.finding(
                        self.rule, node, fi,
                        f"Python `{kind}` over traced value(s) "
                        f"{sorted(hits)} in a jitted body — use lax.cond/"
                        "lax.while_loop/jnp.where"))
        return out

    @staticmethod
    def _arrayish_params(fi) -> set:
        """Params used as arrays in the body: fed to a jnp/lax/jax call
        or subscripted (`x[...]`, not `x.attr[...]`)."""
        arrayish = set()
        for node in ast.walk(fi.node):
            if isinstance(node, ast.Call):
                root = callee_chain(node.func).split(".", 1)[0]
                if root not in ("jnp", "lax", "jax"):
                    continue
                for arg in list(node.args) + \
                        [kw.value for kw in node.keywords]:
                    # names only reached through an attribute read
                    # (hp.lambda_l1, meta.num_bin) are config access,
                    # not array use
                    attr_roots = {id(sub.value) for sub in ast.walk(arg)
                                  if isinstance(sub, ast.Attribute)}
                    for sub in ast.walk(arg):
                        if isinstance(sub, ast.Name) and \
                                sub.id in fi.params and \
                                id(sub) not in attr_roots:
                            arrayish.add(sub.id)
            elif isinstance(node, ast.Subscript) and \
                    isinstance(node.value, ast.Name) and \
                    node.value.id in fi.params:
                arrayish.add(node.value.id)
        return arrayish

    def _traced_names(self, expr: ast.AST) -> set:
        """Bare names whose runtime VALUE the statement branches on.

        `x is None`, `isinstance(x, T)`, `x.shape[0]` and `range(x.ndim)`
        are static at trace time and excluded.
        """
        if isinstance(expr, ast.BoolOp):
            names = set()
            for v in expr.values:
                names |= self._traced_names(v)
            return names
        if isinstance(expr, ast.UnaryOp) and isinstance(expr.op, ast.Not):
            return self._traced_names(expr.operand)
        if isinstance(expr, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in expr.ops):
            return set()
        if isinstance(expr, ast.Call):
            chain = callee_chain(expr.func)
            if chain in ("isinstance", "callable", "hasattr", "getattr",
                         "len", "enumerate", "zip", "range"):
                names = set()
                for a in expr.args:
                    names |= self._traced_names(a)
                return names
        names = set()
        stat_parents = set()
        for sub in ast.walk(expr):
            if (isinstance(sub, ast.Attribute) and
                    sub.attr in STATIC_ARRS and
                    isinstance(sub.value, ast.Name)):
                stat_parents.add(id(sub.value))
        for sub in ast.walk(expr):
            if isinstance(sub, ast.Name) and id(sub) not in stat_parents:
                names.add(sub.id)
        return names


class RecompileHazardRule:
    """JL003: retrace/recompile hazards at jit boundaries."""

    rule = "JL003"

    def visit(self, ctx) -> List:
        out = []
        # (a) hazardous arguments at call sites of known jit bindings
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            key = self._binding_key(node.func)
            binding = ctx.jit_bindings.get(key)
            if binding is None or binding.get("has_static"):
                continue
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                label = self._hazard_label(arg)
                if label:
                    out.append(ctx.finding(
                        self.rule, node, ctx.enclosing(node),
                        f"jitted `{key}` called with a {label} argument but "
                        "bound without static_argnums/static_argnames — "
                        "every distinct value retraces"))
                    break
        # (b) jax.jit(...) constructed inside a loop body (nested loops
        # must not multiply-report the same call site)
        seen = set()
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.For, ast.While)):
                continue
            for sub in ast.walk(node):
                if (isinstance(sub, ast.Call) and id(sub) not in seen and
                        callee_chain(sub.func).split(".")[-1] in
                        ("jit", "pjit")):
                    seen.add(id(sub))
                    out.append(ctx.finding(
                        self.rule, sub, ctx.enclosing(sub),
                        "jax.jit(...) inside a loop builds a fresh "
                        "compilation cache every pass — hoist it out"))
        return out

    @staticmethod
    def _binding_key(func: ast.AST):
        if isinstance(func, ast.Name):
            return func.id
        if (isinstance(func, ast.Attribute) and
                isinstance(func.value, ast.Name) and
                func.value.id == "self"):
            return "self." + func.attr
        return None

    @staticmethod
    def _hazard_label(arg: ast.AST):
        if isinstance(arg, (ast.Dict, ast.DictComp)):
            return "dict"
        if isinstance(arg, ast.Call) and callee_chain(arg.func) == "dict":
            return "dict"
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            return "str"
        return None


class WideningDtypeRule:
    """JL004: dtype-widening literals in kernel files (x64 promotion)."""

    rule = "JL004"
    # *_like constructors inherit dtype from the template array, so a
    # float fill value cannot promote — they are deliberately absent
    JNP_CTORS = {"array", "asarray", "full", "zeros", "ones", "arange",
                 "linspace"}

    def visit(self, ctx) -> List:
        if not ctx.kernel:
            return []
        out = []
        for fi in ctx.jit_funcs:
            for node in ast.walk(fi.node):
                if ctx.enclosing(node) is not fi:  # innermost scope only
                    continue
                if isinstance(node, ast.Attribute) and \
                        node.attr == "float64":
                    base = callee_chain(node).rpartition(".")[0]
                    if base in NUMPY_ALIASES | {"jnp", "jax.numpy"}:
                        out.append(ctx.finding(
                            self.rule, node, fi,
                            f"`{base}.float64` in a kernel file widens the "
                            "f32 hot path (and promotes everything it "
                            "touches under x64)"))
                elif isinstance(node, ast.Call):
                    base, _, attr = callee_chain(node.func).rpartition(".")
                    if base not in ("jnp", "jax.numpy") or \
                            attr not in self.JNP_CTORS:
                        continue
                    kwargs = {kw.arg for kw in node.keywords}
                    dtype_pos = len(node.args) > 1 and attr in (
                        "array", "asarray", "zeros", "ones")
                    has_float_lit = any(
                        isinstance(a, ast.Constant) and
                        isinstance(a.value, float) for a in node.args) or any(
                        isinstance(a, (ast.List, ast.Tuple)) and any(
                            isinstance(e, ast.Constant) and
                            isinstance(e.value, float) for e in a.elts)
                        for a in node.args)
                    if attr == "full" and len(node.args) > 1:
                        # second positional is the FILL VALUE (it decides
                        # the dtype); a positional dtype sits at index 2
                        has_float_lit = (isinstance(node.args[1],
                                                    ast.Constant) and
                                         isinstance(node.args[1].value,
                                                    float))
                        dtype_pos = len(node.args) > 2
                    if has_float_lit and "dtype" not in kwargs and \
                            not dtype_pos:
                        out.append(ctx.finding(
                            self.rule, node, fi,
                            f"`jnp.{attr}` with a float literal and no "
                            "explicit dtype promotes to f64 under "
                            "jax_enable_x64 — pass dtype=jnp.float32"))
        return out


class UnsyncedTimingRule:
    """JL005: timing around async dispatch without a completion barrier."""

    rule = "JL005"

    def visit(self, ctx) -> List:
        out = []
        for fi in ctx.all_funcs:
            if fi.is_lambda:
                continue
            timing, sections, dispatches, synced = [], [], False, False
            for node in ast.walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                # a nested def's timing/dispatch/barriers belong to the
                # nested function's own visit, not this scope's tally
                if ctx.enclosing(node) is not fi:
                    continue
                chain = callee_chain(node.func)
                base, _, attr = chain.rpartition(".")
                if chain in TIMING_CALLS:
                    timing.append(node)
                elif (attr == "section" and "timer" in base.lower() and
                        not any(kw.arg == "sync" for kw in node.keywords)):
                    sections.append(node)
                if any(s in chain for s in SYNC_ATTRS):
                    synced = True
                elif base in NUMPY_ALIASES and attr in ("asarray", "array"):
                    synced = True  # host conversion IS a barrier
                elif (chain in ("float", "int") and len(node.args) == 1 and
                        _wraps_dispatch(node)):
                    synced = True  # float(jnp.sum(x)) — the bench barrier
                elif (isinstance(node.func, ast.Attribute) and
                        attr in ("item", "tolist")):
                    synced = True
                if not dispatches:
                    root = chain.split(".", 1)[0]
                    if root == "jnp" or chain.startswith("jax.numpy"):
                        dispatches = True
                    elif root == "jax" and not any(
                            s in chain for s in SYNC_ATTRS) and \
                            ".config" not in chain:
                        dispatches = True
                    elif self._calls_jitted(ctx, node.func):
                        dispatches = True
            if not dispatches:
                continue
            if len(timing) >= 2 and not synced:
                out.append(ctx.finding(
                    self.rule, timing[1], fi,
                    "wall-clock timing around jax dispatch without "
                    "block_until_ready/device fetch — this measures "
                    "dispatch, not execution (utils/timer.py contract)"))
            for sec in sections:
                if not synced:
                    out.append(ctx.finding(
                        self.rule, sec, fi,
                        "timer.section(...) around jax dispatch without "
                        "sync= — the section charges dispatch time only "
                        "(utils/timer.py contract)"))
        return out

    @staticmethod
    def _calls_jitted(ctx, func: ast.AST) -> bool:
        key = RecompileHazardRule._binding_key(func)
        return key is not None and key in ctx.jit_bindings


ALL_RULES = (HostSyncRule, TracerLeakRule, RecompileHazardRule,
             WideningDtypeRule, UnsyncedTimingRule)
RULE_IDS = tuple(r.rule for r in ALL_RULES)
