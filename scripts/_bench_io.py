"""Shared bench-artifact IO for the serving scripts (ISSUE 8/9).

ONE copy of the session-driver contract: every `bench_logs/SERVING*.json`
writer goes through `write_record` (mkdir + pretty JSON + the stdout
echo the driver tails) and classifies failures through
`classify_status` (one grammar: transient device symptoms are
"device_unreachable", anything else "no_result") — three scripts
drifting on this grammar is the bug class the helper removes.

Status grammar (ISSUE 9 adds "degraded"):

- "measured"           — real numbers from the intended (device) route
- "degraded"           — the run completed but the serving tier ended on
  the host-walk fallback route: the numbers are REAL but are NOT device
  numbers (`status_for` maps a server's `stats()` to this); every
  SERVING*.json writer also carries a boolean `degraded` field
- "device_unreachable" — transient device symptoms; says nothing about
  the code under test
- "no_result"          — anything else

Deliberately jax-free: bench_serving_ab.py runs pure-ctypes.
"""
from __future__ import annotations

import json
import os

STATUSES = ("measured", "degraded", "device_unreachable", "no_result")


def write_record(path: str, record: dict) -> dict:
    """Write one status-bearing record and echo it for the driver."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(record, f, indent=1)
        f.write("\n")
    print(json.dumps(record), flush=True)
    return record


def classify_status(exc: BaseException) -> str:
    """The failure grammar: "device_unreachable" only for
    transient device symptoms (the 0.0 says nothing about the code
    under test), "no_result" otherwise."""
    from lightgbm_tpu.robustness.retry import is_transient_error
    return "device_unreachable" if is_transient_error(exc) \
        else "no_result"


def status_for(server_stats: dict | None) -> str:
    """Completion status for a run that produced numbers: "measured" on
    the intended route, "degraded" when the serving tier ended on the
    host-walk fallback (``stats()["degraded"]``). Writers without a
    device server pass None."""
    if server_stats and server_stats.get("degraded"):
        return "degraded"
    return "measured"


def read_previous_measured(path: str) -> dict | None:
    """Last MEASURED record at ``path``, if any — either the file
    itself (a legacy record without "status" WAS a measurement) or the
    measurement a previous failure run already stashed under
    "previous", so consecutive failure runs never discard it.
    "degraded" records deliberately do NOT bank: their numbers came off
    the host fallback, not the route this file claims to measure."""
    if not os.path.exists(path):
        return None
    try:
        with open(path, encoding="utf-8") as f:
            prev = json.load(f)
    except (OSError, ValueError):
        return None
    if prev.get("status", "measured") == "measured":
        return prev
    nested = prev.get("previous")
    return nested if isinstance(nested, dict) else None
