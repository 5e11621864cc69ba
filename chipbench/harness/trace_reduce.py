"""From a profiler trace to device metrics.

A trace is read into three interval lists on one clock, in nanoseconds:
the operations that ran on each chip (the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane), the programs they belong to (its ``XLA
Modules`` line) and the benchmark's own host annotations (``bench.*``
events of the ``/host:CPU`` plane). The reduction then gives

* busy time: the union of the operation intervals inside the window,
  averaged over the chips, and the idle share of the window;
* device time per program, by the program's name without its id;
* idle gaps by what the host was doing: each stretch of the window in
  which no operation ran is charged to the innermost ``bench.*``
  annotation open at its midpoint.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import gzip
import json
import os
import re

HOST_PREFIX = "bench."
WINDOW_EVENT = "bench.window"


@dataclasses.dataclass
class Trace:
    ops: dict          # chip -> [(start_ns, end_ns, name)]
    modules: dict      # chip -> [(start_ns, end_ns, name)]
    host: list         # [(start_ns, end_ns, name)]

    def to_json(self) -> dict:
        return {"ops": {str(k): v for k, v in self.ops.items()},
                "modules": {str(k): v for k, v in self.modules.items()},
                "host": self.host}

    @staticmethod
    def from_json(d: dict) -> "Trace":
        def tup(lst):
            return [(int(a), int(b), str(n)) for a, b, n in lst]
        return Trace({int(k): tup(v) for k, v in d["ops"].items()},
                     {int(k): tup(v) for k, v in d["modules"].items()},
                     tup(d["host"]))


_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def _op_name(e) -> str:
    """An operation's name; for a custom call (a Pallas kernel) followed by
    its stats, which carry the JAX op path (``.../ensemble_mlp_forward/
    pallas_call``) that names the kernel."""
    if "custom" not in e.name:
        return e.name
    return " ".join([e.name] + [str(v) for _, v in e.stats])


def load_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            if m and line.name in ("XLA Ops", "XLA Modules"):
                dst = ops if line.name == "XLA Ops" else modules
                name = _op_name if line.name == "XLA Ops" else (
                    lambda e: e.name)
                dst.setdefault(int(m.group(1)), []).extend(
                    (int(e.start_ns), int(e.start_ns + e.duration_ns),
                     name(e)) for e in line.events)
            elif plane.name.startswith("/host:"):
                host.extend((int(e.start_ns),
                             int(e.start_ns + e.duration_ns), e.name)
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return Trace(ops, modules, host)


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def save_extract(trace: Trace, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace.to_json(), f)


def load_extract(path: str) -> Trace:
    with gzip.open(path, "rt") as f:
        return Trace.from_json(json.load(f))


def cut(trace: Trace, lo: int, hi: int) -> Trace:
    """The events of ``trace`` that overlap [lo, hi], as recorded, with the
    ``bench.window`` annotation set to [lo, hi]: a small piece of a chip's
    trace for the reduction's tests."""
    def keep(events):
        return [e for e in events if e[1] > lo and e[0] < hi]
    host = [(lo, hi, WINDOW_EVENT)] + [e for e in keep(trace.host)
                                        if e[2] != WINDOW_EVENT]
    return Trace({k: keep(v) for k, v in trace.ops.items()},
                 {k: keep(v) for k, v in trace.modules.items()}, host)


def window_of(trace: Trace) -> tuple[int, int]:
    """The traced window: the ``bench.window`` annotation."""
    w = [(a, b) for a, b, n in trace.host if n == WINDOW_EVENT]
    if not w:
        raise ValueError("the trace has no bench.window annotation")
    return w[0]


def merge(intervals, lo: int, hi: int) -> list:
    """Union of ``intervals`` clipped to [lo, hi], sorted and disjoint."""
    out = []
    for a, b, *_ in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_ns(trace: Trace, lo: int, hi: int) -> float:
    """Busy nanoseconds inside [lo, hi], averaged over the chips."""
    if not trace.ops:
        return 0.0
    return sum(sum(b - a for a, b in merge(v, lo, hi))
               for v in trace.ops.values()) / len(trace.ops)


def program_name(module: str) -> str:
    """``jit_observe_fn(123)`` -> ``jit_observe_fn``."""
    return re.sub(r"\(\d+\)$", "", module).strip()


def program_times(trace: Trace, lo: int, hi: int) -> dict:
    """Program name -> (events, device seconds) inside [lo, hi], over all
    chips."""
    out: dict = collections.defaultdict(lambda: [0, 0.0])
    for events in trace.modules.values():
        for a, b, n in events:
            if a >= lo and b <= hi:
                out[program_name(n)][0] += 1
                out[program_name(n)][1] += (b - a) * 1e-9
    return {k: tuple(v) for k, v in out.items()}


def op_times(trace: Trace, lo: int, hi: int, pattern: str) -> tuple:
    """(events, device seconds) of operations whose name matches
    ``pattern`` inside [lo, hi], over all chips."""
    rx = re.compile(pattern)
    n, s = 0, 0.0
    for events in trace.ops.values():
        for a, b, name in events:
            if a >= lo and b <= hi and rx.search(name):
                n += 1
                s += (b - a) * 1e-9
    return n, s


def idle_by_host(trace: Trace, lo: int, hi: int) -> dict:
    """Idle seconds of the first chip inside [lo, hi], by the innermost
    host annotation open at each gap's midpoint (``service`` where none
    but the window is open)."""
    chip = min(trace.ops) if trace.ops else None
    busy = merge(trace.ops[chip], lo, hi) if chip is not None else []
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    # host annotations are nested (one thread): sweep the gaps in order
    # with a stack of the annotations open at each midpoint
    spans = sorted(((a, b, n) for a, b, n in trace.host
                    if n != WINDOW_EVENT), key=lambda s: (s[0], -s[1]))
    out: dict = collections.defaultdict(float)
    i, stack = 0, []
    for a, b in gaps:
        mid = (a + b) // 2
        while i < len(spans) and spans[i][0] <= mid:
            while stack and stack[-1][1] <= spans[i][0]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] <= mid:
            stack.pop()
        name = stack[-1][2] if stack else "service"
        out[name.removeprefix(HOST_PREFIX)] += (b - a) * 1e-9
    return dict(out)


def reduce(trace: Trace) -> dict:
    lo, hi = window_of(trace)
    busy = busy_ns(trace, lo, hi)
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy * 1e-9,
            "idle_pct": 100.0 * (1.0 - busy / max(hi - lo, 1)),
            "programs": program_times(trace, lo, hi),
            "idle_by_host": idle_by_host(trace, lo, hi),
            "lo": lo, "hi": hi}


def top(d: dict, n: int = 10) -> list:
    """[[name, seconds], ...] of the ``n`` largest entries."""
    items = [(k, v[1] if isinstance(v, tuple) else v) for k, v in d.items()]
    return [[k, v] for k, v in sorted(items, key=lambda p: -p[1])[:n]]
