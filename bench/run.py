"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration,
traffic, loop and per-layer readers are the files under ``bench/`` named
after it (see ``bench/harness.py``).  Set-up (imports, inputs and weights
from the seed, warm-up of every shape the traffic uses) ends at the first
timed operation; then the loop measures for ``--seconds``.  Once the
window has closed, what it produced is compared with a plain reference.

With ``--trace 0`` the result's ``metrics`` are the cell's end-to-end
metrics; with ``--trace 1`` the same run is made with the profiler open
over part of the window, and ``metrics`` are its per-layer metrics, read
from the benchmark's spans, the program's counters and the device trace.

The last lines on standard error, and the ``checks`` key that comes last
in the result, give each number compared beside its limit.  The last line
of standard output is the result, one JSON object.  Without a TPU, or with
fewer chips than the cell asks for, the run fails and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import pathlib      # noqa: E402
import sys          # noqa: E402
import tempfile     # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / "bench" / ".jax_cache"


def _fail(msg: str, code: int = 1) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return code


def use_program() -> None:
    """Put the system under test on the path and keep JAX's compile cache
    at a fixed path inside the checkout, for every compile."""
    sys.path.insert(0, str(ROOT / "src"))
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    os.environ["REPRO_IFUNC_LIB_DIR"] = str(ROOT / "ifunc_libs")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def execute(cell, seed: int, seconds: float, trace: bool, devices, *,
            control: bool = False, keep_trace: str | None = None,
            t_process: float = T_PROCESS, peak: dict | None = None) -> dict:
    """Drive ``cell`` once on ``devices`` and return its result object.
    ``peak`` defaults to the peaks table's row for the devices' kind."""
    from bench import harness as H
    from bench import trace_reduce as TR

    spans = H.Spans()
    run = H.Run(cell, seed, seconds, devices, t_process, spans,
                control=control)
    kind = devices[0].device_kind
    peak = H.peaks(kind) if peak is None else peak
    loop = H.load_module("loops", cell.traffic["loop"])
    tmp = None
    if trace:
        tmp = tempfile.TemporaryDirectory(prefix="bench_trace_")
        tr = cell.traffic
        run.trace = H.TraceWindow(spans, keep_trace or tmp.name,
                                  min(tr["trace_start_s"], seconds / 3),
                                  min(tr["trace_seconds"], seconds / 2))
    try:
        with run.compiles:
            out = loop.run(run)
        if run.in_window is not None:
            out.notes.append(f"in the window {run.in_window[0]} programs "
                             f"traced, {run.in_window[1]} compiled")
        device = {"platform": devices[0].platform, "kind": kind,
                  "count": len(devices),
                  "memory_peak_bytes": run.memory_peak_bytes}
        result = {"correct": out.correct, "attempted": out.attempted,
                  "failed": out.failed}
        if not trace:
            values = dict(out.metrics, setup_s=run.setup_s)
            result["metrics"] = {}
            for m in cell.end_to_end:
                # "<name>.<qualifier>" is the loop's "<name>" in this cell
                v = values.get(m["name"], values.get(m["name"].split(".")[0]))
                if v is not None:
                    result["metrics"][m["name"]] = {"value": v,
                                                    "unit": m["unit"]}
        else:
            ev = TR.load_events(TR.find_xplane(run.trace.log_dir))
            red = TR.reduce(ev)
            rd = H.Readings(spans, run.trace.counts, out.records, red, peak,
                            cell.config, cell.traffic)
            metrics = {}
            for m in cell.per_layer:
                v = H.load_module("metrics", m["name"]).read(rd)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            result["metrics"] = metrics
            device.update(busy_s=red.busy_s, window_s=red.window_s)
            result["breakdown"] = {"device_ops": red.top_ops(10),
                                   "idle_gaps": red.idle_gaps[:10]}
        result["device"] = device
        result["notes"] = out.notes
        result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                            for c in out.checks}
        return result
    finally:
        if tmp is not None:
            tmp.cleanup()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: compare the control (the reference one precision "
                         "below the configuration's) in the program's place; "
                         "its run reads correct false")
    ap.add_argument("--keep-trace", default=None,
                    help="write the profiler trace here and keep it")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        return _fail(f"no system under test: {ROOT / 'src' / 'repro'} is "
                     f"missing", 2)
    use_program()
    from bench import harness as H

    try:
        cell = H.find_cell(args.workload)
    except H.BenchError as e:
        return _fail(str(e), 2)
    import jax

    if jax.default_backend() != "tpu":
        return _fail(f"needs a TPU, JAX found {jax.default_backend()!r}")
    devices = jax.devices()[:cell.chips]
    if len(devices) < cell.chips:
        return _fail(f"{cell.name} asks for {cell.chips} chips, JAX sees "
                     f"{len(jax.devices())}")
    try:
        H.peaks(devices[0].device_kind)
    except H.BenchError as e:
        return _fail(str(e))

    result = execute(cell, args.seed, args.seconds, bool(args.trace), devices,
                     control=bool(args.control), keep_trace=args.keep_trace)
    for note in result.pop("notes"):
        print(f"bench: {note}", file=sys.stderr)
    print(f"bench: correct = {result['correct']}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"bench: check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
