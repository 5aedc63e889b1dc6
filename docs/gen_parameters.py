"""Regenerate docs/Parameters.md from the config registry.

The registry in lightgbm_tpu/config.py is the single source of truth
(mirroring how the reference generates config_auto.cpp from config.h doc
comments); this script renders it as user documentation:

    python docs/gen_parameters.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from lightgbm_tpu.config import (_CHOICES, _NOTES, _P,  # noqa: E402
                                 _UNIMPLEMENTED_WHEN)


def _type_name(t):
    if isinstance(t, str):
        return {"list_int": "list of int", "list_float": "list of float",
                "list_str": "list of string"}.get(t, t)
    return t.__name__ if t is not bool else "bool"


def _fmt_default(typ, d):
    if d is None:
        return "None"
    if typ is bool:
        return "true" if d else "false"
    if isinstance(d, list):
        return "[]" if not d else ",".join(str(x) for x in d)
    if d == "":
        return '""'
    return str(d)


def _fmt_check(check):
    if not check:
        return ""
    lo, hi, lo_inc, hi_inc = check
    parts = []
    if lo is not None:
        parts.append(f"{'>=' if lo_inc else '>'} {lo}")
    if hi is not None:
        parts.append(f"{'<=' if hi_inc else '<'} {hi}")
    return ", constraint: " + " and ".join(parts) if parts else ""


def main() -> str:
    lines = [
        "# Parameters",
        "",
        "All parameters of the framework, generated from the registry in",
        "`lightgbm_tpu/config.py` (the counterpart of the reference's",
        "`docs/Parameters.rst` generated from `config.h`). Aliases resolve",
        "exactly like the reference's `_ConfigAliases`; unknown parameters",
        "warn, and parameters whose feature is not implemented yet warn",
        "loudly instead of silently doing nothing.",
        "",
        f"Total: {len(_P)} parameters.",
        "",
    ]
    for name, (typ, default, aliases, check) in _P.items():
        lines.append(f"### `{name}`")
        lines.append("")
        bits = [f"type: {_type_name(typ)}",
                f"default: `{_fmt_default(typ, default)}`"]
        entry = ", ".join(bits) + _fmt_check(check)
        lines.append(f"- {entry}")
        if aliases:
            lines.append("- aliases: " +
                         ", ".join(f"`{a}`" for a in aliases))
        if name in _CHOICES:
            lines.append("- options: " +
                         ", ".join(f"`{c}`" for c in _CHOICES[name]))
        if name in _NOTES:
            lines.append(f"- {_NOTES[name]}")
        if name in _UNIMPLEMENTED_WHEN:
            lines.append("- **note**: accepted for compatibility; the "
                         "underlying feature is not implemented yet and "
                         "setting it warns at construction")
        lines.append("")
    return "\n".join(lines) + "\n"


if __name__ == "__main__":
    out_path = os.path.join(os.path.dirname(__file__), "Parameters.md")
    text = main()
    with open(out_path, "w") as f:
        f.write(text)
    print(f"wrote {out_path} ({text.count(chr(10))} lines)")
