"""jaxlint engine: AST jit-scope resolution, suppression, baseline diff.

Promotes the repo's ad-hoc lint precedent (scripts/r_lint.py structural R
gate, scripts/body_opcount.py HLO proxy) into a real static-analysis pass
over the Python/JAX sources. Pure stdlib — importable (and fast) without
jax, so the CLI runs anywhere, including the hardware-free CI image.

Jit-scope resolution (which functions count as "traced"):

1. functions decorated with ``@jax.jit`` / ``@jit`` / ``@pjit`` or a
   ``partial(jax.jit, ...)`` form;
2. functions passed by name to ``jax.jit(...)`` — including through one
   level of local assignment (``grow = make_x(...); jax.jit(grow)``);
3. callables handed to the traced higher-order ops (``lax.while_loop``,
   ``lax.cond``, ``lax.scan``, ``lax.fori_loop``, ``lax.switch``,
   ``vmap``, ``grad``, ...);
4. nested functions of "grower factories": any function whose CALL result
   is passed to ``jax.jit`` anywhere in the scanned tree (e.g.
   ``jax.jit(make_tree_grower(...))`` in models/gbdt.py marks the nested
   defs of ``make_tree_grower`` in core/grower.py) — the factory body
   itself runs at trace-setup time and is NOT jit scope;
5. transitively: functions called by simple name (or ``self.method``)
   from jit-scope code in the same module.

Suppression: ``# jaxlint: disable=JL001[,JL005]`` (or ``disable=all``) on
the flagged line, on its own line directly above, or on the enclosing
``def`` line (which suppresses the rule for the whole function).

Baseline: findings fingerprint on (file, rule, scope qualname, normalized
source line, occurrence) — stable across unrelated line drift — and
``jaxlint_baseline.json`` records the accepted pre-existing set so only
NEW findings gate (mirroring the reference repo's lint-gates-CI model).
"""
from __future__ import annotations

import ast
import hashlib
import json
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Set, Tuple

from .rules import ALL_RULES, RULE_IDS, callee_chain

BASELINE_NAME = "jaxlint_baseline.json"
JIT_TAILS = {"jit", "pjit"}
# traced higher-order ops -> their CALLABLE argument positions. Operand
# positions must NOT be treated as callables: a Name bound from
# ``helper(...)`` sitting in an operand slot (``init = helper(x);
# lax.while_loop(cond, body, init)``) would wrongly mark ``helper`` a
# factory and exempt its body from jit scope.
TRACE_HOFS = {
    "while_loop": (0, 1), "fori_loop": (2,), "cond": (1, 2),
    "scan": (0,), "switch": (1,), "map": (0,),
    "associative_scan": (0,), "vmap": (0,), "pmap": (0,), "grad": (0,),
    "value_and_grad": (0,), "jacfwd": (0,), "jacrev": (0,),
    "checkpoint": (0,), "remat": (0,), "custom_vjp": (0,),
    "custom_jvp": (0,),
}
# files whose jit-scope code is the compute hot path (JL004 applies)
KERNEL_PATTERNS = ("lightgbm_tpu/ops/", "core/grower.py",
                   "core/level_grower.py")
# capture only the comma-separated rule list so a plain-word reason after
# it ("# jaxlint: disable=JL001 trace-time probe") can't swallow the token.
# `conlint:` is the concurrency pass's tag (analysis/concurrency.py);
# one regex serves both passes, so either tag suppresses either family.
_SUPPRESS_RE = re.compile(
    r"#\s*(?:jax|con)lint:\s*disable="
    r"([A-Za-z0-9_]+(?:\s*,\s*[A-Za-z0-9_]+)*)")


def _local_call_map(tree: ast.AST) -> Dict[str, str]:
    """One level of local dataflow: name -> callee tail of the Call it
    was assigned from (``grow = make_x(...)`` -> {"grow": "make_x"})."""
    local_calls: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and \
                isinstance(node.value, ast.Call):
            cal = callee_chain(node.value.func).rpartition(".")[2]
            for tgt in node.targets:
                if isinstance(tgt, ast.Name) and cal:
                    local_calls[tgt.id] = cal
    return local_calls


def _factory_from_jit_arg(arg: ast.AST,
                          local_calls: Dict[str, str]) -> Optional[str]:
    """Factory name F when a jit argument is ``F(...)`` or a local bound
    from ``F(...)``; None otherwise."""
    if isinstance(arg, ast.Call):
        return callee_chain(arg.func).rpartition(".")[2] or None
    if isinstance(arg, ast.Name):
        return local_calls.get(arg.id)
    return None


@dataclass
class FuncInfo:
    node: ast.AST                 # FunctionDef / AsyncFunctionDef / Lambda
    qualname: str
    params: Set[str]
    def_line: int
    is_lambda: bool = False
    parent: Optional["FuncInfo"] = None


@dataclass
class Finding:
    rule: str
    path: str                     # repo-relative posix path
    line: int
    col: int
    scope: str
    message: str
    line_text: str
    occ: int = 0                  # disambiguates identical lines in a scope

    @property
    def fingerprint(self) -> str:
        key = "|".join((self.path, self.rule, self.scope,
                        self.line_text.strip(), str(self.occ)))
        return hashlib.sha1(key.encode()).hexdigest()[:16]

    def format(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: {self.rule} "
                f"[{self.scope}] {self.message}")


class FileContext:
    """Everything the rule visitors need about one source file."""

    def __init__(self, rel: str, src: str, tree: ast.Module,
                 factory_names: Set[str],
                 extra_seeds: Optional[Set[str]] = None):
        self.rel = rel
        self.src = src
        self.tree = tree
        self.lines = src.splitlines()
        self.kernel = any(p in rel for p in KERNEL_PATTERNS)
        self.suppressions = _collect_suppressions(self.lines)
        self.all_funcs: List[FuncInfo] = []
        self._by_name: Dict[str, List[FuncInfo]] = {}
        self._func_of_node: Dict[int, FuncInfo] = {}
        self._parents: Dict[int, ast.AST] = {}
        self._collect_funcs()
        self.jit_bindings = _collect_jit_bindings(tree)
        self.factory_names = factory_names
        self._precompute_callgraph()
        self._collect_static_seeds()
        self.jit_funcs: List[FuncInfo] = []
        self.resolve(extra_seeds or set())
        self._occ_seen: Dict[Tuple, int] = {}

    # -- construction ---------------------------------------------------
    def _collect_funcs(self) -> None:
        def walk(node, qual, parent_fi):
            for child in ast.iter_child_nodes(node):
                self._parents[id(child)] = node
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef, ast.Lambda)):
                    name = getattr(child, "name", "<lambda>")
                    q = f"{qual}.{name}" if qual else name
                    fi = FuncInfo(
                        node=child, qualname=q,
                        params=_param_names(child),
                        def_line=child.lineno,
                        is_lambda=isinstance(child, ast.Lambda),
                        parent=parent_fi)
                    self.all_funcs.append(fi)
                    self._by_name.setdefault(name, []).append(fi)
                    self._func_of_node[id(child)] = fi
                    walk(child, q, fi)
                elif isinstance(child, ast.ClassDef):
                    q = f"{qual}.{child.name}" if qual else child.name
                    walk(child, q, parent_fi)
                else:
                    walk(child, qual, parent_fi)
        walk(self.tree, "", None)

    def _precompute_callgraph(self) -> None:
        """One AST walk per function: ids of nested function nodes plus
        the simple names it calls (bare ``f(...)`` and ``self.m(...)``).
        resolve() is then pure set algebra, so the cross-module fixpoint
        can re-resolve scopes without re-walking any tree."""
        self._nested: Dict[int, List[int]] = {}
        self._calls_bare: Dict[int, Set[str]] = {}
        self._calls_any: Dict[int, Set[str]] = {}
        for fi in self.all_funcs:
            nested: List[int] = []
            bare: Set[str] = set()
            any_: Set[str] = set()
            for sub in ast.walk(fi.node):
                if sub is not fi.node and isinstance(
                        sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                    nested.append(id(sub))
                if isinstance(sub, ast.Call):
                    if isinstance(sub.func, ast.Name):
                        bare.add(sub.func.id)
                        any_.add(sub.func.id)
                    elif (isinstance(sub.func, ast.Attribute) and
                            isinstance(sub.func.value, ast.Name) and
                            sub.func.value.id == "self"):
                        any_.add(sub.func.attr)
            self._nested[id(fi.node)] = nested
            self._calls_bare[id(fi.node)] = bare
            self._calls_any[id(fi.node)] = any_

    def _collect_static_seeds(self) -> None:
        """Seed-independent module scan (runs once): jit decorators,
        jit/HOF call sites, and locally-discovered factories. May grow
        ``self.factory_names`` (``grow = make_x(...); jax.jit(grow)``)."""
        self._static_seed_ids: Set[int] = set()

        def seed_name(name: str) -> None:
            for fi in self._by_name.get(name, ()):
                self._static_seed_ids.add(id(fi.node))

        def seed_arg(arg: ast.AST, local_calls: Dict[str, str]) -> None:
            if isinstance(arg, ast.Lambda):
                self._static_seed_ids.add(id(arg))
            elif isinstance(arg, ast.Name):
                if arg.id in self._by_name:
                    seed_name(arg.id)
                elif arg.id in local_calls:
                    self.factory_names.add(local_calls[arg.id])
            elif isinstance(arg, (ast.List, ast.Tuple)):
                # lax.switch takes a SEQUENCE of branch callables
                for e in arg.elts:
                    seed_arg(e, local_calls)

        local_calls = _local_call_map(self.tree)

        for fi in self.all_funcs:
            for dec in getattr(fi.node, "decorator_list", ()):
                if _mentions_jit(dec):
                    self._static_seed_ids.add(id(fi.node))
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            tail = callee_chain(node.func).rpartition(".")[2]
            if tail in JIT_TAILS and node.args:
                arg = node.args[0]
                if isinstance(arg, ast.Call):
                    fname = _factory_from_jit_arg(arg, local_calls)
                    if fname:
                        self.factory_names.add(fname)
                else:
                    seed_arg(arg, local_calls)
            elif tail in TRACE_HOFS:
                for idx in TRACE_HOFS[tail]:
                    if idx < len(node.args):
                        seed_arg(node.args[idx], local_calls)

    def resolve(self, extra_seeds: Set[str]) -> None:
        """(Re)compute ``jit_funcs`` for the given cross-module seed
        names. Cheap — no AST walks — so the repo fixpoint calls it
        repeatedly on the same context."""
        # factory BODIES run at trace-setup time and are never jit scope
        # (their nested defs are) — a traced function calling a factory
        # by name must not drag the factory body in, same-module or
        # cross-module. An explicit @jit decorator still wins (it sits
        # in _static_seed_ids).
        factory_ids = {id(fi.node)
                       for name in self.factory_names
                       for fi in self._by_name.get(name, ())}
        seeds: Set[int] = set(self._static_seed_ids)
        for name in extra_seeds:
            for fi in self._by_name.get(name, ()):
                if id(fi.node) not in factory_ids:
                    seeds.add(id(fi.node))
        # factory nested defs are jit scope (the factory body is not)
        for name in self.factory_names:
            for fi in self._by_name.get(name, ()):
                seeds.update(self._nested[id(fi.node)])

        # transitive closure over same-module simple calls
        changed = True
        while changed:
            changed = False
            for fi in self.all_funcs:
                nid = id(fi.node)
                if nid not in seeds:
                    continue
                for sub_id in self._nested[nid]:
                    if sub_id not in seeds:
                        seeds.add(sub_id)
                        changed = True
                for name in self._calls_any[nid]:
                    for cal in self._by_name.get(name, ()):
                        cal_id = id(cal.node)
                        if cal_id not in seeds and \
                                cal_id not in factory_ids:
                            seeds.add(cal_id)
                            changed = True
        self.jit_funcs = [fi for fi in self.all_funcs
                          if id(fi.node) in seeds]

    def traced_call_names(self) -> Set[str]:
        """Bare names called from this file's jit-scope code — candidates
        for cross-module traced functions (e.g. ops/split.py's scan entry
        points, called from core/grower.py's jitted body)."""
        names: Set[str] = set()
        for fi in self.jit_funcs:
            names |= self._calls_bare[id(fi.node)]
        return names

    # -- services for rules ---------------------------------------------
    def enclosing(self, node: ast.AST) -> Optional[FuncInfo]:
        cur = node
        while cur is not None:
            fi = self._func_of_node.get(id(cur))
            if fi is not None:
                return fi
            cur = self._parents.get(id(cur))
        return None

    def _comment_only(self, line: int) -> bool:
        return (0 < line <= len(self.lines) and
                self.lines[line - 1].lstrip().startswith("#"))

    def _suppressed(self, rule: str, anchor: int) -> bool:
        """Disable comment on the anchor line, or in the contiguous
        comment block directly above it."""
        def hit(line: int) -> bool:
            sup = self.suppressions.get(line)
            return bool(sup and ("all" in sup or rule in sup))

        if hit(anchor):
            return True
        ln = anchor - 1
        while ln > 0 and self._comment_only(ln):
            if hit(ln):
                return True
            ln -= 1
        return False

    def finding(self, rule: str, node: ast.AST, fi: Optional[FuncInfo],
                message: str) -> Optional[Finding]:
        line = getattr(node, "lineno", 1)
        col = getattr(node, "col_offset", 0)
        # suppression anchors: the flagged line, the first line of the
        # enclosing statement (multi-line calls), and the enclosing def
        # line (whole-function suppression); each anchor also honors a
        # comment block directly above it
        stmt = node
        while stmt is not None and not isinstance(stmt, ast.stmt):
            stmt = self._parents.get(id(stmt))
        anchors = {line}
        if stmt is not None:
            anchors.add(stmt.lineno)
        if fi is not None:
            anchors.add(fi.def_line)
        text = (self.lines[line - 1] if 0 < line <= len(self.lines)
                else "")
        scope = fi.qualname if fi else "<module>"
        # count the occurrence BEFORE the suppression check: suppressing
        # one of two identical flagged lines must not re-key the
        # survivor's occ (baseline fingerprints stay stable)
        key = (rule, scope, text.strip())
        occ = self._occ_seen.get(key, 0)
        self._occ_seen[key] = occ + 1
        for anchor in anchors:
            if self._suppressed(rule, anchor):
                return None
        return Finding(rule=rule, path=self.rel, line=line, col=col,
                       scope=scope, message=message, line_text=text,
                       occ=occ)


def _param_names(node: ast.AST) -> Set[str]:
    a = node.args
    names = [p.arg for p in
             list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return {n for n in names if n not in ("self", "cls")}


def _mentions_jit(dec: ast.AST) -> bool:
    """Decorator expression references jit: @jit, @jax.jit,
    @partial(jax.jit, ...), @functools.partial(jit, static_argnums=...)"""
    for sub in ast.walk(dec):
        if isinstance(sub, ast.Name) and sub.id in JIT_TAILS:
            return True
        if isinstance(sub, ast.Attribute) and sub.attr in JIT_TAILS:
            return True
    return False


def _collect_jit_bindings(tree: ast.Module) -> Dict[str, dict]:
    """Names/attributes bound to a ``jax.jit(...)`` result, with whether
    the binding declared static_argnums/static_argnames (JL003/JL005)."""
    bindings: Dict[str, dict] = {}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Assign) and
                isinstance(node.value, ast.Call)):
            continue
        tail = callee_chain(node.value.func).rpartition(".")[2]
        if tail not in JIT_TAILS:
            continue
        has_static = any(kw.arg in ("static_argnums", "static_argnames")
                         for kw in node.value.keywords)
        for tgt in node.targets:
            key = None
            if isinstance(tgt, ast.Name):
                key = tgt.id
            elif (isinstance(tgt, ast.Attribute) and
                    isinstance(tgt.value, ast.Name) and
                    tgt.value.id == "self"):
                key = "self." + tgt.attr
            if key:
                bindings[key] = {"has_static": has_static,
                                 "line": node.lineno}
    return bindings


def _collect_suppressions(lines: List[str]) -> Dict[int, Set[str]]:
    out: Dict[int, Set[str]] = {}
    for i, ln in enumerate(lines, 1):
        m = _SUPPRESS_RE.search(ln)
        if m:
            out[i] = {tok.strip().upper() if tok.strip().lower() != "all"
                      else "all"
                      for tok in m.group(1).split(",") if tok.strip()}
    return out


# ---------------------------------------------------------------------------
# driving: factory pre-pass, per-file lint, repo run
# ---------------------------------------------------------------------------

def collect_factory_names(trees: Dict[str, ast.Module]) -> Set[str]:
    """Pass 1: names F where ``jit(F(...))`` (or ``x = F(...); jit(x)``)
    appears anywhere — their nested defs are jit scope in every module.
    Takes pre-parsed trees so the repo pass parses each file once."""
    names: Set[str] = set()
    for rel, tree in trees.items():
        local_calls = _local_call_map(tree)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            if callee_chain(node.func).rpartition(".")[2] not in JIT_TAILS:
                continue
            if not node.args:
                continue
            fname = _factory_from_jit_arg(node.args[0], local_calls)
            if fname:
                names.add(fname)
    return names


def _lint_ctx(ctx: FileContext) -> List[Finding]:
    """Run every rule over an already-built FileContext."""
    findings: List[Finding] = []
    for rule_cls in ALL_RULES:
        for f in rule_cls().visit(ctx):
            if f is not None:
                findings.append(f)
    findings.sort(key=lambda f: (f.line, f.col, f.rule))
    return findings


def lint_source(src: str, rel: str,
                factory_names: Optional[Set[str]] = None,
                extra_seeds: Optional[Set[str]] = None) -> List[Finding]:
    """Lint one source string; ``rel`` decides kernel-file rules (JL004)."""
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [Finding(rule="JL000", path=rel, line=e.lineno or 1, col=0,
                        scope="<module>", message=f"syntax error: {e.msg}",
                        line_text="")]
    return _lint_ctx(FileContext(
        rel, src, tree, set(factory_names) if factory_names else set(),
        extra_seeds))


def default_targets(root: str) -> List[str]:
    cands = [os.path.join(root, "lightgbm_tpu"),
             os.path.join(root, "scripts")]
    return [c for c in cands if os.path.exists(c)]


def iter_py_files(paths) -> List[str]:
    files = []
    for p in paths:
        if os.path.isdir(p):
            for base, _dirs, fns in os.walk(p):
                if "__pycache__" in base:
                    continue
                for fn in sorted(fns):
                    if fn.endswith(".py"):
                        files.append(os.path.join(base, fn))
        elif p.endswith(".py"):
            files.append(p)
    return files


def run_paths(paths, root: str) -> List[Finding]:
    """Multi-pass lint over files/dirs; paths become root-relative in
    findings so fingerprints are machine-independent.

    Pass 1 collects jit-factory names globally; then jit scopes are
    resolved to a cross-module fixpoint: bare names called from traced
    code in any file seed same-named module functions everywhere (how
    ops/split.py's scan entry points — called from core/grower.py's
    jitted body — enter jit scope)."""
    import builtins
    builtin_names = set(dir(builtins))
    files = iter_py_files(paths)
    sources: Dict[str, str] = {}
    trees: Dict[str, ast.Module] = {}
    for f in files:
        rel = os.path.relpath(os.path.abspath(f),
                              os.path.abspath(root)).replace(os.sep, "/")
        try:
            with open(f, encoding="utf-8") as fh:
                sources[rel] = fh.read()
        except OSError:
            continue
    syntax_errs: Dict[str, SyntaxError] = {}
    for rel in list(sources):
        try:
            trees[rel] = ast.parse(sources[rel])
        except SyntaxError as e:
            syntax_errs[rel] = e
    factories = collect_factory_names(trees)
    seeds: Set[str] = set()
    ctxs = {rel: FileContext(rel, sources[rel], tree, set(factories))
            for rel, tree in trees.items()}  # built once; resolve() is cheap
    while True:  # cross-module fixpoint: seeds grow monotonically and are
        # bounded by the repo's function names, so this terminates
        called: Set[str] = set()
        for ctx in ctxs.values():
            called |= ctx.traced_call_names()
        called -= builtin_names | factories   # factory bodies: trace-setup
        if called <= seeds:
            break
        seeds |= called
        for ctx in ctxs.values():
            ctx.resolve(seeds)
    findings: List[Finding] = []
    for rel in sorted(sources):
        if rel in ctxs:
            findings.extend(_lint_ctx(ctxs[rel]))
        else:
            e = syntax_errs[rel]
            findings.append(Finding(
                rule="JL000", path=rel, line=e.lineno or 1, col=0,
                scope="<module>", message=f"syntax error: {e.msg}",
                line_text=""))
    return findings


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------

def default_baseline_path(root: str) -> str:
    return os.path.join(root, BASELINE_NAME)


def load_baseline_records(path: str) -> List[dict]:
    if not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as fh:
        data = json.load(fh)
    return list(data.get("findings", ()))


def load_baseline(path: str) -> Set[str]:
    return {e["fingerprint"] for e in load_baseline_records(path)}


def save_baseline(path: str, findings: List[Finding],
                  keep_records: List[dict] = ()) -> None:
    """Write the accepted-findings baseline. ``keep_records`` carries
    existing entries for files OUTSIDE the linted path set, so a partial
    `--update-baseline path/...` run can't wipe the rest of the repo's
    accepted findings."""
    records = [
        {"fingerprint": f.fingerprint, "rule": f.rule, "file": f.path,
         "scope": f.scope, "line_text": f.line_text.strip()}
        for f in findings] + list(keep_records)
    records.sort(key=lambda e: (e.get("file", ""), e.get("rule", ""),
                                e.get("line_text", "")))
    data = {
        "version": 1,
        "tool": "jaxlint",
        "note": ("accepted pre-existing findings; only NEW findings gate. "
                 "Regenerate with: python scripts/jaxlint.py "
                 "--update-baseline"),
        "findings": records,
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(data, fh, indent=1)
        fh.write("\n")


def diff_against_baseline(findings: List[Finding], baseline: Set[str]
                          ) -> Tuple[List[Finding], List[Finding]]:
    """-> (new, known)"""
    new, known = [], []
    for f in findings:
        (known if f.fingerprint in baseline else new).append(f)
    return new, known


# ---------------------------------------------------------------------------
# CLI (scripts/jaxlint.py is a thin wrapper over this)
# ---------------------------------------------------------------------------

def main(argv: Optional[List[str]] = None, root: Optional[str] = None
         ) -> int:
    import argparse
    parser = argparse.ArgumentParser(
        prog="jaxlint",
        description="JAX-hazard static analysis (rules JL001-JL005; "
                    "see lightgbm_tpu/analysis/rules.py)")
    parser.add_argument("paths", nargs="*",
                        help="files/dirs to lint (default: the package + "
                             "scripts)")
    parser.add_argument("--baseline", default=None,
                        help=f"baseline json (default: <root>/"
                             f"{BASELINE_NAME})")
    parser.add_argument("--update-baseline", action="store_true",
                        help="accept current findings as the new baseline")
    parser.add_argument("--no-baseline", action="store_true",
                        help="ignore the baseline; report everything as new")
    parser.add_argument("--list", action="store_true", dest="list_all",
                        help="print known (baselined) findings too")
    args = parser.parse_args(argv)

    if root is None:
        root = os.getcwd()
    # explicit paths resolve against cwd first, then root — and a scan
    # that matches no files must FAIL, not report a green gate
    paths, missing = [], []
    for p in args.paths:
        if os.path.exists(p):
            paths.append(p)
        elif os.path.exists(os.path.join(root, p)):
            paths.append(os.path.join(root, p))
        else:
            missing.append(p)
    if missing:
        print(f"jaxlint: path(s) not found: {', '.join(missing)}")
        return 2
    if not args.paths:
        paths = default_targets(root)
    if not iter_py_files(paths):
        print("jaxlint: no .py files under the given path(s) — "
              "nothing was linted")
        return 2
    findings = run_paths(paths, root)
    findings_real = [f for f in findings if f.rule != "JL000"]
    syntax_errors = [f for f in findings if f.rule == "JL000"]

    bl_path = args.baseline or default_baseline_path(root)
    if args.update_baseline:
        if syntax_errors:
            for f in syntax_errors:
                print(f.format())
            print("jaxlint: refusing to update the baseline while files "
                  "fail to parse — JL000 findings are never baselined")
            return 1
        keep: List[dict] = []
        if args.paths:
            # partial update: only the scanned files' entries are
            # replaced; accepted findings elsewhere must survive
            scanned = {
                os.path.relpath(os.path.abspath(f), os.path.abspath(root))
                .replace(os.sep, "/") for f in iter_py_files(paths)}
            keep = [e for e in load_baseline_records(bl_path)
                    if e.get("file") not in scanned]
        save_baseline(bl_path, findings_real, keep)
        kept_note = f" (+{len(keep)} kept from unscanned files)" \
            if keep else ""
        print(f"jaxlint: baseline updated with {len(findings_real)} "
              f"finding(s){kept_note} -> {bl_path}")
        return 0

    baseline = set() if args.no_baseline else load_baseline(bl_path)
    new, known = diff_against_baseline(findings_real, baseline)
    for f in syntax_errors:
        print(f.format())
    for f in new:
        print(f.format())
    if args.list_all:
        for f in known:
            print(f"{f.format()}  [known]")
    by_rule: Dict[str, int] = {}
    for f in findings_real:
        by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
    breakdown = " ".join(f"{r}={by_rule.get(r, 0)}" for r in RULE_IDS)
    print(f"jaxlint: {len(findings_real)} finding(s): {len(new)} new, "
          f"{len(known)} known (baselined) [{breakdown}]")
    if new:
        print("jaxlint: new findings — fix them, add a targeted "
              "`# jaxlint: disable=<RULE>` with a reason, or accept via "
              "--update-baseline")
    return 1 if (new or syntax_errors) else 0
