"""The profiler trace of a window, and its reduction to numbers.

``Tracer`` records the window with ``jax.profiler`` (Python tracer off)
and marks it with a ``bench.window`` annotation; the harness's spans
(``bench.join``, ``bench.step``, ``bench.await_arrival``) are
``TraceAnnotation``s on the same clock.  ``reduce`` reads the
``.xplane.pb`` with ``jax.profiler.ProfileData`` and gives, over the
window:

* ``busy_s``: the union of the intervals in which an XLA op ran on a
  device, averaged over the devices;
* ``modules``: device seconds per XLA module (``jit_prefill``,
  ``jit_decode``, ...), the program id stripped from the name;
* ``top_ops``: the device ops that took most time;
* ``idle``: device idle seconds by what the host was doing (the harness
  span that covers the gap, else ``host``).
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW = "bench.window"


class Tracer:
    def __init__(self):
        import jax

        self.jax = jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self._window = None

    def annotate(self, name: str):
        return self.jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        self.jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._window = self.annotate(WINDOW)
        self._window.__enter__()

    def stop(self) -> None:
        self._window.__exit__(None, None, None)
        self.jax.profiler.stop_trace()

    def path(self) -> str:
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.dir}")
        return found[0]

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(iv: List[Tuple[float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name).strip()


def reduce(path: str, top: int = 10) -> Dict[str, object]:
    """Reduce one trace file; times in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    devices = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans[ev.name[len(SPAN_PREFIX):]].append(
                        (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9))
    if not devices:
        raise ValueError(f"no device plane in {path}; planes: {[p.name for p in data.planes]}")
    if not spans.get("window"):
        raise ValueError(f"no {WINDOW} annotation in {path}")
    lo, hi = spans.pop("window")[0]
    busy_per_device, modules, ops = [], defaultdict(float), defaultdict(float)
    busy_all: List[Tuple[float, float]] = []
    for plane in devices:
        intervals = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                a, b = ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9
                if b <= lo or a >= hi:
                    continue
                d = min(b, hi) - max(a, lo)
                if line.name == OPS_LINE:
                    intervals.append((a, b))
                    ops[ev.name] += d
                else:
                    modules[_module_name(ev.name)] += d
        busy = _clip(_union(intervals), lo, hi)
        busy_per_device.append(sum(b - a for a, b in busy))
        if not busy_all:
            busy_all = busy
    idle = _attribute_idle(busy_all, lo, hi, spans)
    n = len(devices)
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy_per_device) / n,
        "devices": n,
        "modules": {k: v / n for k, v in sorted(modules.items(), key=lambda kv: -kv[1])},
        "top_ops": [[k, v / n] for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle": idle,
    }


def _attribute_idle(busy, lo, hi, spans) -> Dict[str, float]:
    """Idle seconds of the first device, split by the harness span that
    overlaps each gap; what no span covers is ``host``."""
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    # the harness's spans follow one another and do not nest
    marks = sorted((a, b, name) for name, iv in spans.items() for a, b in iv)
    out: Dict[str, float] = defaultdict(float)
    first = 0
    for ga, gb in gaps:
        covered = 0.0
        while first < len(marks) and marks[first][1] <= ga:
            first += 1
        j = first
        while j < len(marks) and marks[j][0] < gb:
            a, b, name = marks[j]
            d = min(b, gb) - max(a, ga)
            out[name] += d
            covered += d
            j += 1
        out["host"] += max(0.0, (gb - ga) - covered)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def breakdown(reduced: Dict[str, object], top: int = 10) -> Dict[str, list]:
    """The result line's ``breakdown``: top device ops, and idle seconds
    by host activity."""
    return {"device_ops": [list(x) for x in reduced["top_ops"][:top]],
            "idle_gaps": [[k, v] for k, v in list(reduced["idle"].items())[:top]]}
