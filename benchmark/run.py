"""The benchmark's one entry point.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One new process per run. Everything that belongs to one cell is data or a
file of its own, found by the names in ``BENCHMARK.json`` (see
``README.md``): ``workloads/<cell>.json`` -> its configuration's file ->
that file's ``driver`` -> (traced or not) one reader per metric,
``metrics/<metric>.py``. Nothing here names a cell, a configuration or a
metric.

The last line of standard output is the result object. Without an
accelerator the process exits non-zero before any work and prints none;
``--rehearse`` (never passed by the driver of the checks) cuts the sizes as
the configuration's ``rehearse`` block says and allows the CPU, for the
tests and for trying the harness out.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()          # set-up is counted from here

import argparse                        # noqa: E402
import contextlib                      # noqa: E402
import importlib.util                  # noqa: E402
import json                            # noqa: E402
import math                            # noqa: E402
import os                              # noqa: E402
import shutil                          # noqa: E402
import sys                             # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
EXIT_NO_CHIP = 3


def load_json(path: str):
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def load_module(rel_path: str):
    """Import a file under ``benchmark/`` by its path."""
    path = os.path.join(HERE, rel_path)
    name = "bench_" + rel_path.replace("/", "_").replace(".", "_").replace(
        "-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Spans:
    """The benchmark's own spans: kept in memory on the host clock, and
    written into the profiler's trace as ``bench.<name>`` while one is
    being taken, so that device gaps can be laid against them."""

    def __init__(self, tracing: bool):
        self.tracing = tracing
        self.records: list = []          # (name, start, end), perf_counter

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.tracing:
            import jax
            ann = jax.profiler.TraceAnnotation("bench." + name)
        t0 = time.perf_counter()
        try:
            with ann:
                yield
        finally:
            self.records.append((name, t0, time.perf_counter()))

    def total(self, name: str) -> float:
        return sum(b - a for n, a, b in self.records if n == name)


class CompileCounter:
    """Counts what jax compiles (or fetches from its persistent cache)."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == self.EVENT:
            self.count += 1


class Run:
    """What a driver is handed: the cell, its configuration, the run's
    arguments, the spans and a place on disk that the checkout owns."""

    def __init__(self, args, cell: dict, cfg: dict, workload: dict):
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.rehearse = args.rehearse
        self.cell, self.cfg, self.workload = cell, cfg, workload
        self.spans = Spans(self.trace)
        self.data_dir = os.path.join(CACHE_DIR, "bench_data")
        self.log: list = []              # the program's log lines
        self.load_module = load_module

    def say(self, msg: str) -> None:
        print(f"[bench] {msg}", flush=True)

    def steps(self, steady_s: float) -> int:
        """Window steps, fixed before the clock starts: the traced run's
        few, or as many as fill ``--seconds`` at the warm-up's pace."""
        if self.trace:
            return int(self.workload["trace_steps"])
        return max(1, math.ceil(self.seconds / max(steady_s, 1e-9)))


def judge(readings: dict, limits: dict):
    """The comparison that decides ``correct``: every limit has its
    reading, and every reading is a number at or under its limit. Returns
    (correct, {name: {"value", "limit"}}). The controls and the tests are
    judged by this same function."""
    compared = {name: {"value": value, "limit": limits.get(name)}
                for name, value in readings.items()}
    ok = set(limits) == set(readings) and all(
        c["value"] is not None and c["value"] == c["value"]
        and c["value"] <= c["limit"] for c in compared.values())
    return ok, compared


def device_report(devices, chips: int) -> dict:
    """The device as jax reports it. ``memory_peak_bytes`` is the fullest
    chip's ``peak_bytes_in_use`` (live buffers) plus its
    ``peak_bytes_reserved``: the block that the runtime sets aside for the
    temporaries of the programs it has loaded, which the first counter
    leaves out (``PERF.md`` section 6, finding 2). Both parts are given
    beside the sum."""
    stats = [d.memory_stats() or {} for d in devices[:chips]]
    parts = [(s["peak_bytes_in_use"], s.get("peak_bytes_reserved", 0))
             for s in stats if "peak_bytes_in_use" in s]
    live, reserved = max(parts, key=sum, default=(None, None))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": None if live is None else live + reserved,
            "live_peak_bytes": live, "reserved_peak_bytes": reserved}


def open_cell(args):
    """The cell's entries and files, the compile cache placed, jax imported
    and the chips counted. Returns (manifest, cell, cfg, workload, devices)
    or an exit code."""
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next(w for w in manifest["workloads"]
                if w["name"] == args.workload)
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == cell["config"])
    cfg = load_json(os.path.join(ROOT, cfg_entry["file"]))
    workload = load_json(os.path.join(HERE, "workloads",
                                      cell["name"] + ".json"))
    if args.rehearse:
        cfg = {**cfg, **cfg.get("rehearse", {})}

    # one compile cache, inside the checkout, at a fixed path; set before
    # jax is imported so the program takes it too
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    sys.path[:0] = [p for p in (ROOT, HERE) if p not in sys.path]
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu" and not args.rehearse:
        print("benchmark: jax found no accelerator", file=sys.stderr)
        return EXIT_NO_CHIP
    if len(devices) < cell["chips"]:
        print(f"benchmark: the cell needs {cell['chips']} chips, jax found "
              f"{len(devices)}", file=sys.stderr)
        return EXIT_NO_CHIP
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return manifest, cell, cfg, workload, devices


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    opened = open_cell(args)
    if isinstance(opened, int):
        return opened
    manifest, cell, cfg, workload, devices = opened
    import jax

    run = Run(args, cell, cfg, workload)
    driver = load_module(cfg["driver"])
    compiles = CompileCounter()

    state = driver.setup(run)
    compiles_setup = compiles.count
    trace_dir = os.path.join(CACHE_DIR, "bench_trace", cell["name"])
    if run.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    setup_s = time.perf_counter() - T_START
    try:
        with run.spans("window"):
            result = driver.window(run, state)
    finally:
        if run.trace:
            jax.profiler.stop_trace()
    compiles_window = compiles.count - compiles_setup
    run.say(f"setup_s={setup_s:.3f} window_s={result['seconds']:.3f} "
            f"work={result['work']} compiles_in_setup={compiles_setup} "
            f"compiles_in_window={compiles_window}")

    device = device_report(devices, cell["chips"])
    summary = reducer = None
    if run.trace:
        reducer = load_module("reduce_trace.py")
        summary = reducer.reduce(reducer.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        run.say("trace: " + json.dumps({
            "modules": sorted(summary["module_s"].items(),
                              key=lambda kv: -kv[1])[:8],
            "ops": [[n[:240], t] for n, t in sorted(
                summary["op_s"].items(), key=lambda kv: -kv[1])[:25]],
            "spans": summary["span_s"],
            "planes": {k: v for k, v in summary["planes"].items()
                       if not k.startswith("/host")}}))
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
    peaks_table = load_json(os.path.join(HERE, "peaks.json"))
    if device["kind"] not in peaks_table and not args.rehearse:
        raise KeyError(f"device kind {device['kind']!r} is not in "
                       "benchmark/peaks.json")
    ctx = {
        "setup_s": setup_s, "result": result, "trace": summary,
        "peak_bytes": device["memory_peak_bytes"],
        "peaks": peaks_table.get(device["kind"]),
        "work": driver.work(run, state, result),
        "spans": run.spans, "cfg": cfg, "cell": cell,
    }
    wanted = manifest["per_layer"] if run.trace else manifest["end_to_end"]
    metrics = {}
    for m in wanted:
        if not applies(m, cell["name"]):
            continue
        value = load_module(f"metrics/{m['name']}.py").read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # the comparison with the plain reference: after the window, after the
    # peak was read; the driver frees the program's state first
    readings = driver.compare(run, state, result)
    del state
    correct, compared = judge(readings, workload["limits"])
    correct = correct and result["failed"] == 0

    line = {"correct": bool(correct), "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics,
            "device": device}
    if run.trace:
        line["breakdown"] = reducer.breakdown(summary)
    line["compiles_in_window"] = compiles_window
    line["compared"] = compared
    sys.stdout.flush()
    for name, c in compared.items():
        print(f"compared {name} value={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
