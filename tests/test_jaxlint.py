"""jaxlint static-analysis pass: rule coverage, suppression, baseline.

One fixture snippet per rule ID (JL001-JL005) asserts each rule fires;
suppression tests cover the three anchor positions (same line, comment
line above, enclosing def line) plus ``disable=all``; baseline tests
assert the known/new split and the CLI exit-code contract that gates CI
(exit 0 on no new findings, nonzero when a seeded violation appears).

Pure stdlib on the analysis side — no jax import; the fixtures are
linted as source strings, never executed (only the two repo-wide gate
tests pay the few-second full-package pass).
"""
import os
import subprocess
import sys
import textwrap

import pytest

from lightgbm_tpu.analysis import jaxlint
from lightgbm_tpu.analysis.jaxlint import (
    default_baseline_path,
    diff_against_baseline,
    lint_source,
    load_baseline,
    run_paths,
    save_baseline,
)
from lightgbm_tpu.analysis.rules import RULE_IDS

REPO_ROOT = os.path.join(os.path.dirname(__file__), "..")

# One violation per rule ID. Linted under a kernel-relative path so JL004
# (kernel files only) participates.
FIXTURE = textwrap.dedent('''\
    import time

    import jax
    import jax.numpy as jnp


    @jax.jit
    def host_sync(x):
        return x.item()                      # <- JL001


    @jax.jit
    def tracer_leak(x):
        y = jnp.abs(x)
        if x > 0:                            # <- JL002
            return y
        return -y


    apply_fn = jax.jit(lambda tree, cfg: tree)


    def recompile_hazard(tree):
        return apply_fn(tree, {"lr": 0.1})   # <- JL003


    @jax.jit
    def widening(x):
        return x + jnp.array(1.5)            # <- JL004


    def unsynced_timing(a, b):
        t0 = time.perf_counter()
        out = jnp.dot(a, b)
        t1 = time.perf_counter()             # <- JL005
        return out, t1 - t0
''')
KERNEL_REL = "lightgbm_tpu/ops/_jaxlint_fixture.py"


def _lint(src, rel=KERNEL_REL):
    return lint_source(src, rel)


# ---------------------------------------------------------------------------
# rule firing
# ---------------------------------------------------------------------------

def test_fixture_flags_every_rule_exactly_once():
    findings = _lint(FIXTURE)
    by_rule = {}
    for f in findings:
        by_rule.setdefault(f.rule, []).append(f)
    assert sorted(by_rule) == sorted(RULE_IDS), (
        f"expected one finding per rule, got: "
        f"{[(f.rule, f.line, f.message) for f in findings]}")
    for rule, fs in by_rule.items():
        assert len(fs) == 1, (rule, [(f.line, f.message) for f in fs])
    scopes = {f.rule: f.scope for f in findings}
    assert scopes["JL001"] == "host_sync"
    assert scopes["JL002"] == "tracer_leak"
    assert scopes["JL003"] == "recompile_hazard"
    assert scopes["JL004"] == "widening"
    assert scopes["JL005"] == "unsynced_timing"


def test_jl004_only_fires_in_kernel_files():
    findings = _lint(FIXTURE, rel="lightgbm_tpu/models/_fixture.py")
    assert "JL004" not in {f.rule for f in findings}
    assert {"JL001", "JL002", "JL003", "JL005"} <= {f.rule
                                                    for f in findings}


def test_jl004_like_ctors_never_flag():
    """*_like constructors inherit dtype from the template array — a
    float fill value cannot promote, so full_like must never flag
    (while jnp.full's fill value DOES decide the dtype and does)."""
    src = textwrap.dedent('''\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def inherits(x):
            return jnp.full_like(x, 1.5)

        @jax.jit
        def hazard(x):
            return x + jnp.full((4,), 1.5)

        @jax.jit
        def full_explicit(x):
            return x + jnp.full((4,), 1.5, jnp.float32)
    ''')
    hits = [f for f in _lint(src) if f.rule == "JL004"]
    assert [f.scope for f in hits] == ["hazard"], hits


def test_static_shape_access_is_not_a_tracer_leak():
    src = textwrap.dedent('''\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def ok(x):
            acc = jnp.zeros_like(x)
            if x.shape[0] > 4:
                acc = acc + 1
            for _ in range(x.ndim):
                acc = acc * 2
            return acc
    ''')
    assert [f for f in _lint(src) if f.rule == "JL002"] == []


def test_syntax_error_reports_jl000():
    findings = _lint("def broken(:\n")
    assert [f.rule for f in findings] == ["JL000"]


# ---------------------------------------------------------------------------
# suppression anchors
# ---------------------------------------------------------------------------

SUPPRESS_VARIANTS = {
    "same_line": '''\
        import jax

        @jax.jit
        def f(x):
            return x.item()  # jaxlint: disable=JL001
    ''',
    "line_above": '''\
        import jax

        @jax.jit
        def f(x):
            # jaxlint: disable=JL001 -- deliberate trace-time probe
            return x.item()
    ''',
    "def_line": '''\
        import jax

        @jax.jit
        def f(x):  # jaxlint: disable=JL001
            return x.item()
    ''',
    "disable_all": '''\
        import jax

        @jax.jit
        def f(x):
            return x.item()  # jaxlint: disable=all
    ''',
    # a plain-word reason after the rule list must not defeat the match
    "word_reason": '''\
        import jax

        @jax.jit
        def f(x):
            return x.item()  # jaxlint: disable=JL001 trace time probe
    ''',
}


@pytest.mark.parametrize("variant", sorted(SUPPRESS_VARIANTS))
def test_suppression_honored(variant):
    src = textwrap.dedent(SUPPRESS_VARIANTS[variant])
    assert _lint(src) == [], variant


def test_suppression_is_rule_specific():
    src = textwrap.dedent('''\
        import jax

        @jax.jit
        def f(x):
            return x.item()  # jaxlint: disable=JL002
    ''')
    assert [f.rule for f in _lint(src)] == ["JL001"]


# ---------------------------------------------------------------------------
# baseline semantics
# ---------------------------------------------------------------------------

def test_baseline_splits_known_from_new(tmp_path):
    findings = _lint(FIXTURE)
    bl = tmp_path / "jaxlint_baseline.json"
    save_baseline(str(bl), findings)
    new, known = diff_against_baseline(findings, load_baseline(str(bl)))
    assert new == [] and len(known) == len(RULE_IDS)

    # a freshly introduced violation is NEW; the baselined ones stay known
    seeded = FIXTURE + textwrap.dedent('''\


        @jax.jit
        def fresh(x):
            return x.tolist()
    ''')
    new, known = diff_against_baseline(_lint(seeded),
                                       load_baseline(str(bl)))
    assert len(known) == len(RULE_IDS)
    assert [f.rule for f in new] == ["JL001"]
    assert new[0].scope == "fresh"


def test_fingerprint_stable_when_duplicate_line_is_suppressed():
    """Suppressing the first of two identical flagged lines must not
    re-key the survivor's occurrence counter (else the baseline entry for
    an untouched line goes spuriously 'new')."""
    dup = textwrap.dedent('''\
        import jax

        @jax.jit
        def f(x, out):
            out.append(x.item())
            out.append(x.item())
            return out
    ''')
    both = _lint(dup)
    assert [f.occ for f in both] == [0, 1]
    suppressed_first = dup.replace(
        "    out.append(x.item())",
        "    # jaxlint: disable=JL001\n    out.append(x.item())", 1)
    survivor, = _lint(suppressed_first)
    assert survivor.occ == 1
    assert survivor.fingerprint == both[1].fingerprint


def test_baseline_fingerprint_survives_line_drift():
    shifted = "# a new comment line\n\n" + FIXTURE
    orig = {f.fingerprint for f in _lint(FIXTURE)}
    assert {f.fingerprint for f in _lint(shifted)} == orig


# ---------------------------------------------------------------------------
# CLI contract (what scripts/jaxlint.py and scripts/check.sh gate on)
# ---------------------------------------------------------------------------

def test_cli_exit_codes_roundtrip(tmp_path, capsys):
    target = tmp_path / "mod.py"
    target.write_text(textwrap.dedent('''\
        import jax

        @jax.jit
        def f(x):
            return x.item()
    '''))
    argv = [str(target)]
    assert jaxlint.main(argv, root=str(tmp_path)) == 1      # new finding
    assert jaxlint.main(argv + ["--update-baseline"],
                        root=str(tmp_path)) == 0            # accept
    assert jaxlint.main(argv, root=str(tmp_path)) == 0      # now known
    out = capsys.readouterr().out
    assert "1 known" in out

    target.write_text(target.read_text() + textwrap.dedent('''\


        @jax.jit
        def g(x):
            return x.tolist()
    '''))
    assert jaxlint.main(argv, root=str(tmp_path)) == 1      # seeded -> gate


def test_update_baseline_refuses_syntax_errors(tmp_path, capsys):
    """--update-baseline must not report success over an unparsable tree:
    JL000 findings are never baselined, so accepting would leave the very
    next plain run red on an untouched tree."""
    (tmp_path / "ok.py").write_text(textwrap.dedent('''\
        import jax

        @jax.jit
        def f(x):
            return x.item()
    '''))
    (tmp_path / "broken.py").write_text("def broken(:\n")
    argv = [str(tmp_path), "--update-baseline"]
    assert jaxlint.main(argv, root=str(tmp_path)) == 1
    out = capsys.readouterr().out
    assert "JL000" in out and "refusing" in out
    assert not (tmp_path / "jaxlint_baseline.json").exists()


def test_partial_update_baseline_keeps_unscanned_files(tmp_path, capsys):
    """`--update-baseline some/path` must only replace the scanned
    files' entries — accepted findings elsewhere survive (a partial
    update must never turn the gate red on untouched files)."""
    (tmp_path / "a.py").write_text(textwrap.dedent('''\
        import jax

        @jax.jit
        def fa(x):
            return x.item()
    '''))
    (tmp_path / "b.py").write_text(textwrap.dedent('''\
        import jax

        @jax.jit
        def fb(x):
            return x.tolist()
    '''))
    root = str(tmp_path)
    assert jaxlint.main([root, "--update-baseline"], root=root) == 0
    # partial update over b.py only: a.py's accepted finding must survive
    assert jaxlint.main([str(tmp_path / "b.py"), "--update-baseline"],
                        root=root) == 0
    capsys.readouterr()
    assert jaxlint.main([root], root=root) == 0, (
        "partial --update-baseline wiped entries for unscanned files:\n"
        + capsys.readouterr().out)
    out = capsys.readouterr().out
    assert "2 known" in out


def test_repo_is_clean_against_checked_in_baseline(capsys):
    """Acceptance gate: `python scripts/jaxlint.py` exits 0 on the repo."""
    assert os.path.exists(default_baseline_path(REPO_ROOT)), (
        "jaxlint_baseline.json missing — regenerate with "
        "`python scripts/jaxlint.py --update-baseline`")
    rc = jaxlint.main([], root=REPO_ROOT)
    out = capsys.readouterr().out
    assert rc == 0, f"new jaxlint findings in the repo:\n{out}"


def test_repo_seeded_violation_gates(tmp_path):
    """Acceptance gate: a seeded JL001-JL005 violation exits nonzero."""
    seeded = tmp_path / "seeded.py"
    seeded.write_text(textwrap.dedent('''\
        import jax

        @jax.jit
        def seeded_violation(x):
            return x.item()
    '''))
    rc = jaxlint.main([str(seeded)], root=REPO_ROOT)
    assert rc == 1


def test_hof_operand_args_are_not_factories():
    """Only the CALLABLE positions of a lax higher-order op mark
    factories/traced callees. A helper whose RESULT feeds an operand
    slot (`init = helper(x); lax.while_loop(cond, body, init)`) must
    stay in jit scope — conflating the two exempted real host-sync
    hazards from the gate."""
    src = textwrap.dedent('''\
        import jax
        from jax import lax

        def helper(x):
            return x.item()

        def cond_fn(c):
            return c[0] < 3

        def body_fn(c):
            return (c[0] + 1, c[1])

        @jax.jit
        def grow(x):
            init = helper(x)
            return lax.while_loop(cond_fn, body_fn, (0, init))
    ''')
    hits = [f for f in _lint(src) if f.rule == "JL001"]
    assert [f.scope for f in hits] == ["helper"], hits


def test_cli_wrapper_never_imports_jax_or_the_package():
    """The gate must run on jax-free images and never touch an
    accelerator: loading scripts/jaxlint.py may not pull in jax
    or lightgbm_tpu's package root (whose __init__ imports jax)."""
    script = os.path.abspath(
        os.path.join(REPO_ROOT, "scripts", "jaxlint.py"))
    probe = textwrap.dedent(f'''
        import runpy, sys
        before = set(sys.modules)
        runpy.run_path({script!r}, run_name="loaded_for_test")
        new = set(sys.modules) - before
        bad = [m for m in new
               if m == "jax" or m.startswith(("jax.", "jaxlib"))
               or m == "lightgbm_tpu" or m.startswith("lightgbm_tpu.")]
        assert not bad, f"CLI imported {{sorted(bad)}}"
        print("CLEAN")
    ''')
    out = subprocess.run([sys.executable, "-c", probe],
                         capture_output=True, text=True)
    assert "CLEAN" in out.stdout, out.stderr


def test_run_paths_resolves_cross_module_jit_scope(tmp_path):
    """A function called by bare name from another module's jitted body
    enters jit scope (how ops/split.py is reached from core/grower.py)."""
    (tmp_path / "kernels.py").write_text(textwrap.dedent('''\
        def scan_feature(h):
            return h.item()
    '''))
    (tmp_path / "driver.py").write_text(textwrap.dedent('''\
        import jax
        from kernels import scan_feature

        @jax.jit
        def grow(h):
            return scan_feature(h)
    '''))
    findings = run_paths([str(tmp_path)], str(tmp_path))
    hits = [f for f in findings if f.rule == "JL001"]
    assert any(f.path == "kernels.py" and f.scope == "scan_feature"
               for f in hits), findings
