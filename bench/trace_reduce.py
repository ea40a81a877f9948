"""Reduce a profiler trace to what the per-layer readers need.

Two steps, so the second can be checked on a small recorded trace:

* :func:`load_events` reads the ``.xplane.pb`` that ``jax.profiler``
  writes (with nothing but JAX) into plain lists: the operations each
  device ran (the ``XLA Ops`` line of every ``/device:`` plane), the
  programs (its ``XLA Modules`` line) and the host's annotated spans.
  An operation belongs to the program whose run on the same device
  contains its start.
* :func:`reduce` cuts those to the traced window, which the benchmark
  marks with a ``bench.window`` annotation, and sums: the union of busy
  intervals per device, the time and count of each operation and each
  program, and the longest idle gaps with the host span that covered
  most of each.

All times are seconds; device totals are summed over the devices traced
and divided by their number, so a reader gets the time of one device.

    python bench/trace_reduce.py <file.xplane.pb>   # a summary by hand
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import sys

WINDOW = "bench.window"
OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


@dataclasses.dataclass
class Events:
    """Raw events on the trace's clock, in nanoseconds."""

    # device plane name -> [(op name, start_ns, dur_ns)]
    device_ops: dict
    # device plane name -> [(program, start_ns, dur_ns)]
    device_modules: dict
    # [(name, start_ns, dur_ns, stats dict)] from every host thread
    host: list


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def load_events(path: str) -> Events:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: dict = {}
    mods: dict = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name in OPS_LINES:
                    rows = ops.setdefault(plane.name, [])
                    for e in line.events:
                        rows.append((e.name, float(e.start_ns),
                                     float(e.duration_ns)))
                elif line.name in MODULE_LINES:
                    rows = mods.setdefault(plane.name, [])
                    for e in line.events:
                        rows.append((e.name, float(e.start_ns),
                                     float(e.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.name, float(e.start_ns),
                                     float(e.duration_ns), dict(e.stats)))
    return Events(ops, mods, host)


@dataclasses.dataclass
class Reduced:
    window_s: float
    n_devices: int
    busy_s: float                      # mean over devices
    ops: dict                          # (program, op) -> [seconds, count] per device
    programs: dict                     # program -> [seconds, count] per device
    idle_gaps: list                    # [(host activity, seconds)], longest first

    def op_time(self, match) -> tuple[float, int]:
        """(seconds, calls) per device of every op for which
        ``match(op_name)`` is true."""
        s = c = 0
        for (_, name), (t, n) in self.ops.items():
            if match(name):
                s += t
                c += n
        return s, c

    def program_time(self, match) -> tuple[float, int]:
        """(seconds, runs) per device of every program whose name holds
        one of the substrings in ``match``."""
        s = c = 0
        for name, (t, n) in self.programs.items():
            if any(m in name for m in match):
                s += t
                c += n
        return s, c

    def top_ops(self, k: int = 10) -> list:
        """The ``k`` ops that took most device time: [program/op, s]."""
        rows = sorted(self.ops.items(), key=lambda kv: -kv[1][0])[:k]
        return [[f"{prog}/{short_op(name)}", t] for (prog, name), (t, _)
                in rows]


def short_op(name: str) -> str:
    """``%sweep.3 = f32[..]{..} custom-call(...)`` -> ``%sweep.3
    custom-call``: the instruction and its opcode, without the HLO text."""
    head, _, rest = name.partition(" = ")
    m = re.search(r"[\s)}\]]([a-z][\w-]*)\(", rest)
    return f"{head} {m.group(1)}" if m else head


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: float, b: float, lo: float, hi: float):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def window_bounds(ev: Events) -> tuple[float, float]:
    marks = [(s, s + d) for n, s, d, _ in ev.host if n == WINDOW]
    if not marks:
        raise ValueError("trace holds no bench.window annotation")
    return max(marks, key=lambda m: m[1] - m[0])


def reduce(ev: Events, n_gaps: int = 10) -> Reduced:
    lo, hi = window_bounds(ev)
    planes = sorted(ev.device_ops) or sorted(ev.device_modules)
    n_dev = max(len(planes), 1)
    busy = 0.0
    ops: dict = {}
    gaps_all: list[tuple[float, float]] = []
    for i, plane in enumerate(planes):
        iv = []
        runs = sorted(ev.device_modules.get(plane, []), key=lambda m: m[1])
        starts = [m[1] for m in runs]
        for name, s, d in ev.device_ops.get(plane, []):
            c = _clip(s, s + d, lo, hi)
            if c is None:
                continue
            iv.append(c)
            j = bisect.bisect_right(starts, s) - 1
            prog = (_program_name(runs[j][0])
                    if j >= 0 and s <= runs[j][1] + runs[j][2] else "")
            row = ops.setdefault((prog, name), [0.0, 0])
            row[0] += (c[1] - c[0]) * 1e-9 / n_dev
            row[1] += 1
        if not iv:
            iv = [c for _, s, d in ev.device_modules.get(plane, [])
                  if (c := _clip(s, s + d, lo, hi)) is not None]
        u = _union(iv)
        busy += sum(b - a for a, b in u) * 1e-9 / n_dev
        if i == 0:                     # gaps of the first device
            edges = [lo] + [x for ab in u for x in ab] + [hi]
            gaps_all = [(edges[j], edges[j + 1])
                        for j in range(0, len(edges) - 1, 2)
                        if edges[j + 1] > edges[j]]
    programs: dict = {}
    for plane, rows in ev.device_modules.items():
        for name, s, d in rows:
            c = _clip(s, s + d, lo, hi)
            if c is None:
                continue
            row = programs.setdefault(_program_name(name), [0.0, 0])
            row[0] += (c[1] - c[0]) * 1e-9 / n_dev
            row[1] += 1
    for row in programs.values():
        row[1] = row[1] / n_dev
    for row in ops.values():
        row[1] = row[1] / n_dev
    host = [(n, s, s + d) for n, s, d, _ in ev.host if n != WINDOW]
    gaps = sorted(gaps_all, key=lambda g: g[0] - g[1])[:n_gaps]
    idle = [[_activity(host, a, b), (b - a) * 1e-9] for a, b in gaps]
    return Reduced((hi - lo) * 1e-9, n_dev, busy, ops, programs, idle)


def _program_name(name: str) -> str:
    """``jit_sweep(12345)`` -> ``jit_sweep``."""
    return name.split("(")[0]


def _activity(host, a: float, b: float) -> str:
    """The host span that covers most of [a, b]; the shorter (inner) one
    where two cover it alike."""
    best, key = "host (no span)", (0.0, 0.0)
    for n, s, e in host:
        c = min(b, e) - max(a, s)
        if c > 0 and (c, -(e - s)) > key:
            best, key = n, (c, -(e - s))
    return best


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(argv[0])
    for plane in pd.planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            names: dict = {}
            for e in evs:
                names[e.name] = names.get(e.name, 0) + e.duration_ns
            top = sorted(names.items(), key=lambda kv: -kv[1])[:8]
            print(f"  line {line.name!r}: {len(evs)} events; top "
                  + "; ".join(f"{n} {t / 1e6:.3f}ms" for n, t in top))
            for e in evs[:3]:
                print("    e.g.", e.name, dict(e.stats))
    return 0


if __name__ == "__main__":
    sys.exit(main())
