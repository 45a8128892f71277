"""The device trace: taking a slice of the window and reducing it.

``Slice`` records one interval of the window with JAX's profiler and
marks both ends with a host annotation whose wall-clock time is known,
so that the device's timeline can be laid over the servers' flush
records.  ``reduce`` works on a plain dict of planes, lines and events
(``load`` makes it from an ``.xplane.pb``; the test fixture is such a
dict, cut down), so the same code reads a recorded trace on the CPU.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
import time

ANCHOR = "bench_anchor"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Slice:
    """Trace ``[start, stop]`` (wall clock) from the calling thread."""

    def __init__(self, start: float, stop: float):
        self.start, self.stop = start, stop
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.anchors: list[float] = []
        self.timing: dict = {}
        self._on = False

    def _anchor(self) -> None:
        import jax
        self.anchors.append(time.time())
        with jax.profiler.TraceAnnotation(ANCHOR):
            time.sleep(0.001)

    def run(self) -> None:
        import jax
        time.sleep(max(0.0, self.start - time.time()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        t = time.time()
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._on = True
        self._anchor()
        time.sleep(max(0.0, self.stop - time.time()))
        self._anchor()
        t_stop = time.time()
        self.abort()
        self.timing = {"start_call_s": self.anchors[0] - t,
                       "traced_s": self.anchors[1] - self.anchors[0],
                       "stop_call_s": time.time() - t_stop}

    def abort(self) -> None:
        if self._on:
            import jax
            self._on = False
            jax.profiler.stop_trace()

    def reduce(self, cycles: list[tuple[float, float]]) -> dict | None:
        """The summary of the slice; the trace's files are removed."""
        try:
            paths = glob.glob(os.path.join(
                self.dir, "plugins", "profile", "*", "*.xplane.pb"))
            if not paths or len(self.anchors) != 2:
                return None
            size = os.path.getsize(paths[-1])
            out = reduce(load(paths[-1]), self.anchors, cycles)
            if out is not None:
                out["xplane_bytes"] = size
            return out
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def load(path: str) -> dict:
    """An ``.xplane.pb`` as planes -> lines -> [name, start, duration]
    (nanoseconds), device planes and the anchors' host line only."""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if device or e.name == ANCHOR]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union(spans: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(spans):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _op_name(name: str) -> str:
    """An operation's name as the trace gives it is its whole HLO
    line: keep what is left of `` = `` and, for a custom call, its
    target (the Pallas kernels are ``tpu_custom_call``)."""
    short = name.split(" = ", 1)[0]
    target = re.search(r'custom_call_target="([^"]+)"', name)
    return f"{short} {target.group(1)}" if target else short


def _module_name(name: str) -> str:
    """``jit__fused(123456)`` -> ``jit__fused``: the id is the
    compiled program's, new in every process."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce(data: dict, anchors_unix: list[float],
           cycles: list[tuple[float, float]]) -> dict | None:
    """Busy seconds, module times, every device operation's total by
    name (and the ten with most) and the longest idle gaps of the
    slice between the two anchors.  ``cycles`` are the
    servers' flush cycles as wall-clock (start, end); a gap that one
    of them covers for more than half is the flush's, the rest is
    ingest's.  None where the trace holds no device plane or lacks
    the anchors."""
    marks = sorted(e[1] for p in data["planes"]
                   if not DEVICE_PLANE.match(p["name"])
                   for ln in p["lines"] for e in ln["events"]
                   if e[0] == ANCHOR)
    devices = [p for p in data["planes"]
               if DEVICE_PLANE.match(p["name"])]
    if len(marks) < 2 or not devices:
        return None
    w0, w1 = marks[0], marks[-1]
    # wall clock -> trace nanoseconds, by the first anchor
    def to_trace(t_unix: float) -> float:
        return w0 + (t_unix - anchors_unix[0]) * 1e9
    busy_s = []
    modules: dict[str, list[float]] = {}
    ops: dict[str, float] = {}
    gaps: list[tuple[float, float]] = []
    for p in devices:
        lines = {ln["name"]: ln["events"] for ln in p["lines"]}
        work = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        spans = _union([(max(s, w0), min(s + d, w1))
                        for _, s, d in work if s + d > w0 and s < w1])
        busy_s.append(sum(b - a for a, b in spans) / 1e9)
        for name, s, d in lines.get(MODULES_LINE, []):
            if s >= w0 and s + d <= w1:
                modules.setdefault(_module_name(name), []).append(d)
        for name, s, d in lines.get(OPS_LINE, []):
            if s >= w0 and s + d <= w1:
                key = _op_name(name)
                ops[key] = ops.get(key, 0.0) + d / 1e9
        edges = [w0] + [x for ab in spans for x in ab] + [w1]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    flush = _union([(to_trace(a), to_trace(b)) for a, b in cycles])

    def owner(a: float, b: float) -> str:
        covered = sum(max(0.0, min(b, y) - max(a, x))
                      for x, y in flush)
        return "flush_cycle" if covered > 0.5 * (b - a) else "ingest"
    gaps.sort(key=lambda ab: ab[0] - ab[1])
    ranked = sorted(([k, v] for k, v in ops.items()),
                    key=lambda kv: -kv[1])
    return {
        "busy_s": sum(busy_s) / len(busy_s),
        "window_s": (w1 - w0) / 1e9,
        "devices": len(devices),
        "modules": {k: {"n": len(v), "total_s": sum(v) / 1e9}
                    for k, v in modules.items()},
        # the ten with most time go into the result line's breakdown;
        # a reader that sums a kernel's operations takes them from all
        "device_ops": ranked[:10],
        "device_ops_all": ranked,
        "idle_gaps": [[owner(a, b), (b - a) / 1e9]
                      for a, b in gaps[:10]],
    }
