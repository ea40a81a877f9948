"""What every cell shares: discovery by name, the chip's peaks, exact
percentiles, the benchmark's own spans and the traced window.

Nothing here knows a configuration, a traffic mix or a metric.  A cell is
found in ``BENCHMARK.json`` by its name; its configuration, its traffic,
the loop that drives it and each per-layer reader are files named after
entries there:

    bench/configs/<config>.json     sizes, source, deployment, limits
    bench/traffic/<traffic>.json    parameters + ``"loop": <kind>``
    bench/loops/<kind>.py           ``run(run: Run) -> Outcome``
    bench/metrics/<metric>.py       ``read(r: Readings) -> float | None``
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(RuntimeError):
    """A cell, file or device the benchmark cannot run with."""


# -- discovery ---------------------------------------------------------------


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, base: pathlib.Path = BENCH):
    """Import ``<base>/<kind>/<name>.py`` by path (names may hold dots)."""
    path = base / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no {kind[:-1]} file {path.relative_to(base.parent)}")
    mod_name = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list        # BENCHMARK.json metric entries this cell reports
    per_layer: list


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", "") in e2e_names if "moves" in metric else True


def find_cell(name: str, spec: dict | None = None,
              base: pathlib.Path = BENCH) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files loaded."""
    if spec is None:
        path = base.parent / "BENCHMARK.json"
        if not path.is_file():
            raise BenchError(f"no {path}")
        spec = load_json(path)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    config = load_json(base / "configs" / f"{w['config']}.json")
    traffic = load_json(base / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in spec["end_to_end"] if _reports(m, name, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _reports(m, name, names)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)


# -- the chip's peaks ---------------------------------------------------------


def peaks(device_kind: str, base: pathlib.Path = BENCH) -> dict:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    table = load_json(base / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def roofline_s(flops: float, nbytes: float, peak: dict,
               flops_key: str = "bf16_flops") -> tuple[float, str]:
    """Least time the chip could take for this work, and which bound sets
    it: ``"compute"`` or ``"memory"``."""
    tc = flops / peak[flops_key]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


# -- statistics ---------------------------------------------------------------


def percentile(values, q: float) -> float:
    """Exact nearest-rank percentile: the smallest sample with at least
    ``q`` percent of all samples at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    k = max(math.ceil(q / 100.0 * len(xs)), 1)
    return float(xs[k - 1])


# -- spans and the traced window ---------------------------------------------


class Spans:
    """The benchmark's own spans around its calls into each layer.

    Off until a traced window opens: the end-to-end runs record nothing.
    While on, each span is kept as ``(name, t0, t1, n)`` on the host's
    ``perf_counter`` clock (``n`` counts the invocations or requests it
    covers) and is written into the profiler's trace as a
    ``TraceAnnotation`` of the same name."""

    def __init__(self):
        self.on = False
        self.rows: list[tuple[str, float, float, int]] = []
        self._null = contextlib.nullcontext()

    def __call__(self, name: str, n: int = 1):
        if not self.on:
            return self._null
        return _Span(self, name, n)

    def total(self, name: str) -> tuple[float, int, int]:
        """(seconds, items, spans) recorded under ``name``."""
        s = n = c = 0
        for nm, t0, t1, k in self.rows:
            if nm == name:
                s += t1 - t0
                n += k
                c += 1
        return s, n, c


class _Span:
    __slots__ = ("spans", "name", "n", "t0", "ann")

    def __init__(self, spans: Spans, name: str, n: int):
        self.spans, self.name, self.n = spans, name, n

    def __enter__(self):
        import jax

        self.ann = jax.profiler.TraceAnnotation(self.name, n=self.n)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.ann.__exit__(*exc)
        self.spans.rows.append((self.name, self.t0, t1, self.n))
        return False


class TraceWindow:
    """Opens the profiler for part of the measured window.

    The loop calls :meth:`poll` once per turn with the seconds elapsed in
    the window; the profiler starts at ``start_s`` and stops at the first
    turn past ``start_s + length_s``.  A ``bench.window`` annotation spans
    exactly the traced interval, so the reduction finds its ends on the
    trace's own clock.  Counters the loop registers with :meth:`counter`
    are read at both ends."""

    def __init__(self, spans: Spans, log_dir: str, start_s: float,
                 length_s: float):
        self.spans, self.log_dir = spans, log_dir
        self.start_s, self.stop_s = start_s, start_s + length_s
        self.state = "before"          # -> "open" -> "closed"
        self.t0 = self.t1 = None
        self._ann = None
        self._counters: dict = {}
        self.counts: dict[str, float] = {}

    def counter(self, name: str, read) -> None:
        self._counters[name] = read

    def poll(self, elapsed: float) -> None:
        if self.state == "before" and elapsed >= self.start_s:
            self._open()
        elif self.state == "open" and elapsed >= self.stop_s:
            self.close()

    def _open(self) -> None:
        import jax

        jax.profiler.start_trace(self.log_dir)
        self._base = {k: f() for k, f in self._counters.items()}
        self._ann = jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        self.spans.on = True
        self.state = "open"

    def close(self) -> None:
        if self.state != "open":
            return
        import jax

        self.spans.on = False
        self.t1 = time.perf_counter()
        self._ann.__exit__(None, None, None)
        self.counts = {k: f() - self._base[k]
                       for k, f in self._counters.items()}
        self._counters.clear()         # they may hold the program's state
        jax.profiler.stop_trace()
        self.state = "closed"


# -- a run --------------------------------------------------------------------

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Compiles:
    """Counts the programs this process traces and compiles (JAX's
    monitoring events), so a run can say whether its window held any."""

    def __init__(self):
        self.traced = self.compiled = 0

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        from jax._src import monitoring

        monitoring.unregister_event_duration_listener(self._on)
        return False

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == TRACE_EVENT:
            self.traced += 1
        elif event == COMPILE_EVENT:
            self.compiled += 1

    def counts(self) -> tuple[int, int]:
        return self.traced, self.compiled


@dataclasses.dataclass
class Run:
    """What a loop is given: the cell, the seed, the window's length, the
    devices, and the spans and traced window (``trace`` is None in an
    end-to-end run)."""

    cell: Cell
    seed: int
    seconds: float
    devices: list
    t_process: float                 # perf_counter at process start
    spans: Spans
    trace: TraceWindow | None = None
    control: bool = False            # compare the control in the program's place
    compiles: Compiles = dataclasses.field(default_factory=Compiles)
    setup_s: float | None = None
    memory_peak_bytes: int | None = None
    in_window: tuple[int, int] | None = None   # (traced, compiled) in it

    def setup_done(self) -> float:
        """Mark the first timed operation: set-up ends here."""
        self.setup_s = time.perf_counter() - self.t_process
        self._at_setup = self.compiles.counts()
        return self.setup_s

    def window_done(self) -> None:
        """Mark the window's close: count what was traced or compiled in
        it (there should be nothing)."""
        now = self.compiles.counts()
        self.in_window = (now[0] - self._at_setup[0],
                          now[1] - self._at_setup[1])

    def read_memory_peak(self) -> int | None:
        """Peak bytes in use on the fullest chip of the cell, so far."""
        peaks_ = []
        for d in self.devices:
            st = d.memory_stats() or {}
            if "peak_bytes_in_use" in st:
                peaks_.append(int(st["peak_bytes_in_use"]))
        self.memory_peak_bytes = max(peaks_) if peaks_ else None
        return self.memory_peak_bytes


@dataclasses.dataclass
class Check:
    """One number compared with its limit: correct while value <= limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a loop returns: counts, end-to-end values by metric name, the
    numbers compared, and whatever its per-layer readers read."""

    attempted: int
    failed: int
    metrics: dict[str, float]
    checks: list[Check]
    records: dict = dataclasses.field(default_factory=dict)
    notes: list[str] = dataclasses.field(default_factory=list)

    @property
    def correct(self) -> bool:
        return (self.failed == 0 and bool(self.checks)
                and all(c.ok for c in self.checks))


@dataclasses.dataclass
class Readings:
    """What a per-layer reader is given."""

    spans: Spans
    counts: dict
    records: dict
    trace: object                    # trace_reduce.Reduced or None
    peak: dict
    config: dict
    traffic: dict
