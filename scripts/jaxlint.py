"""Static-analysis CLI: JAX hazards (JL001-JL005) + concurrency
hazards (CL001-CL005).

Thin wrapper over lightgbm_tpu.analysis.{jaxlint,concurrency} — pure
stdlib, no jax import, so it runs anywhere in a few seconds (same gate
model as scripts/r_lint.py: CI-cheap, zero hardware).

Usage:
  python scripts/jaxlint.py                     # BOTH passes vs baselines
  python scripts/jaxlint.py --pass jax          # JAX hazards only
  python scripts/jaxlint.py --pass concurrency  # lock/threading hazards
  python scripts/jaxlint.py --list              # also print known findings
  python scripts/jaxlint.py --update-baseline   # accept current findings
  python scripts/jaxlint.py path/to/file.py     # lint specific paths

Exit 0: no new findings vs jaxlint_baseline.json /
concurrency_baseline.json (the concurrency baseline additionally
requires every entry to carry a one-line triage reason). Exit 1: new
findings (or syntax errors, or a reasonless concurrency baseline
entry). Suppress a deliberate hazard in source with
`# jaxlint: disable=JL00x` / `# conlint: disable=CL00x` plus a reason.
"""
import importlib.util
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PKG_DIR = os.path.join(REPO_ROOT, "lightgbm_tpu", "analysis")

# Load the analysis package by file path, NOT via `import lightgbm_tpu`:
# the package root's __init__ imports jax (guards hook, Booster surface),
# and this CLI must run on jax-free images and never touch an
# accelerator.
_spec = importlib.util.spec_from_file_location(
    "_jaxlint_analysis", os.path.join(_PKG_DIR, "__init__.py"),
    submodule_search_locations=[_PKG_DIR])
_pkg = importlib.util.module_from_spec(_spec)
sys.modules["_jaxlint_analysis"] = _pkg
_spec.loader.exec_module(_pkg)
jaxlint = importlib.import_module("_jaxlint_analysis.jaxlint")
concurrency = importlib.import_module("_jaxlint_analysis.concurrency")


def _extract_pass(argv):
    """Pop --pass [jax|concurrency|all] (default all) from argv."""
    which = "all"
    out = []
    i = 0
    while i < len(argv):
        a = argv[i]
        if a == "--pass":
            if i + 1 >= len(argv):
                print("jaxlint: --pass needs a value "
                      "(jax|concurrency|all)", file=sys.stderr)
                raise SystemExit(2)
            which = argv[i + 1]
            i += 2
            continue
        if a.startswith("--pass="):
            which = a.split("=", 1)[1]
            i += 1
            continue
        out.append(a)
        i += 1
    if which not in ("jax", "concurrency", "all"):
        print(f"jaxlint: unknown --pass {which!r} "
              "(expected jax|concurrency|all)", file=sys.stderr)
        raise SystemExit(2)
    return which, out


if __name__ == "__main__":
    which, argv = _extract_pass(sys.argv[1:])
    rc = 0
    if which in ("jax", "all"):
        rc = max(rc, jaxlint.main(argv, root=REPO_ROOT))
    if which in ("concurrency", "all"):
        # with no explicit paths the concurrency pass scans its own
        # default set (the ten lock-bearing modules), so running both
        # passes back to back needs no path juggling
        rc = max(rc, concurrency.main(argv, root=REPO_ROOT))
    sys.exit(rc)
