"""Reduce a JAX profiler trace of the window to device time by module.

The trace is the `.xplane.pb` that `jax.profiler.stop_trace` writes.  Its
device planes (`/device:TPU:<n>`) hold a line of XLA modules: one event per
execution of a compiled program, named after the jitted function
(`jit_traverse`, `jit__accumulate_walk_tables`, ...), which is how a layer
file finds its modules.  The host plane holds the harness's own
annotations, `<assembly>/<step>` around each `next()` of `assemble_iter`:
together they are the timed stretches of the window, and each names the
idle gaps inside it.  The check's copies between them fall outside.
"""
from __future__ import annotations

import fnmatch
import glob
import os
import bisect
import re

MODULE_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
_SUFFIX = re.compile(r"\(\d+\)$")
TOP = 10


def module_name(event_name: str) -> str:
    """An XLA module event's program name, without the run id suffix."""
    return _SUFFIX.sub("", event_name).strip()


def matches(name: str, patterns) -> bool:
    return any(fnmatch.fnmatchcase(name, p) for p in patterns)


def union_seconds(intervals) -> float:
    """Seconds covered by a set of (start_ns, end_ns) intervals."""
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total / 1e9


class TraceWindow:
    """Module executions of each device, clipped to the timed stretches,
    and the stretches themselves, each named by its stage."""

    def __init__(self, devices: dict, spans: list):
        self.spans = sorted(spans, key=lambda x: x[1])
        if not self.spans:
            raise ValueError("the trace holds no timed stretch")
        if not devices:
            raise ValueError("the trace holds no device plane")
        starts = [s for _, s, _ in self.spans]
        self.devices = {}
        for d, evs in devices.items():
            clipped = []
            for n, s, t in evs:
                i = max(bisect.bisect_right(starts, s) - 1, 0)
                for _, lo, hi in self.spans[i:]:
                    if lo >= s + t:
                        break
                    if hi > s:
                        clipped.append((n, max(s, lo), min(s + t, hi)))
            self.devices[d] = clipped

    @property
    def window_s(self) -> float:
        return sum(e - s for _, s, e in self.spans) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which a program ran, averaged over the devices."""
        return sum(union_seconds((s, e) for _, s, e in evs)
                   for evs in self.devices.values()) / len(self.devices)

    def module_seconds(self, patterns) -> float:
        """Device seconds of the modules a layer file's patterns match,
        averaged over the devices."""
        return sum(e - s for evs in self.devices.values()
                   for n, s, e in evs if matches(n, patterns)) \
            / 1e9 / len(self.devices)

    def by_module(self) -> dict:
        out = {}
        for evs in self.devices.values():
            for n, s, e in evs:
                out[n] = out.get(n, 0.0) + (e - s) / 1e9 / len(self.devices)
        return out

    def unmatched_s(self, per_layer) -> float:
        """Device seconds in modules that no layer file lists."""
        patterns = [p for m in per_layer
                    for p in m["spec"].get("modules", [])]
        return sum(t for n, t in self.by_module().items()
                   if not matches(n, patterns))

    def idle_by_stage(self) -> dict:
        """Idle seconds of the first device, by the stage of the timed
        stretch each gap lies in."""
        evs = sorted((s, e) for _, s, e in next(iter(self.devices.values())))
        out = {}
        for stage, lo, hi in self.spans:
            cur, idle = lo, 0
            for s, e in evs:
                if e <= lo or s >= hi:
                    continue
                if s > cur:
                    idle += s - cur
                cur = max(cur, e)
            idle += max(hi - cur, 0)
            out[stage] = out.get(stage, 0.0) + idle / 1e9
        return out

    def breakdown(self) -> dict:
        top = lambda d: [[k, v] for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:TOP]]
        return {"device_ops": top(self.by_module()),
                "idle_gaps": top(self.idle_by_stage())}


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def from_planes(planes, spans: dict) -> TraceWindow:
    """Build the window from profiler planes (objects with `name`, `lines`;
    lines with `name`, `events`; events with `name`, `start_ns`,
    `duration_ns`).  `spans` maps the harness's annotation names to stages.
    """
    devices, host = {}, []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            evs = [(module_name(e.name), int(e.start_ns), int(e.duration_ns))
                   for line in plane.lines if line.name == MODULE_LINE
                   for e in line.events]
            devices[plane.name] = evs
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name in spans:
                        s = int(e.start_ns)
                        host.append((spans[e.name], s,
                                     s + int(e.duration_ns)))
    return TraceWindow(devices, host)


def load(trace_dir: str, spans: dict) -> TraceWindow:
    import jax

    data = jax.profiler.ProfileData.from_file(newest_xplane(trace_dir))
    return from_planes(data.planes, spans)
