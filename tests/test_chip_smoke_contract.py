"""The two things the driver holds ``chip_smoke.py`` to without a chip: it
refuses to run on the CPU, and its last line has exactly the contract's keys.
"""
import importlib.util
import json
import os
import subprocess
import sys

REPO = os.path.join(os.path.dirname(__file__), "..")


def test_chip_smoke_refuses_to_run_without_a_chip():
    """On the CPU the chip smoke exits non-zero before any work and
    prints no result line (the pass line needs platform=tpu)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], env=env,
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "platform=cpu" in out.stdout
    assert '"ok"' not in out.stdout and "{" not in out.stdout
    assert "needs a TPU" in out.stderr


def test_chip_smoke_result_line_has_exactly_the_contract_keys():
    """The driver reads the LAST stdout line as a JSON object with exactly
    ``ok`` and ``device`` {platform, kind, count}; the phase reports go on
    the summary line before it, never into this one."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod     # its dataclass looks itself up
    try:
        spec.loader.exec_module(mod)
    finally:
        del sys.modules["chip_smoke"]
    line = mod.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert "\n" not in line
    got = json.loads(line)
    assert got == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert type(got["device"]["count"]) is int
