"""Profiler trace -> device busy time, step-program time and breakdown.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps,
for each device plane (``/device:TPU:n``), the events of its ``XLA Ops``
line (each operation the device ran) and of its ``XLA Modules`` line
(each execution of a compiled program).  ``reduce`` turns those into
numbers; it takes plain tuples, so it is tested without a chip.

* busy: the union of the operation intervals inside the traced window,
  per device, averaged over the devices;
* the step program: the module with the most device time, which is the
  serving step wherever the device does real work; its executions give
  ``step_ms`` and the device seconds the steps took;
* ``breakdown``: the operations with the most device time, and the
  longest idle gaps, each named by the program that ran before it.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Tuple

Event = Tuple[str, int, int]          # (name, start_ns, duration_ns)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """{device plane: {"ops": [...], "modules": [...]}} of one trace."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        lines = {"ops": [], "modules": []}
        for line in plane.lines:
            key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
            if key is None:
                continue
            lines[key] = [(_short(e.name), int(e.start_ns),
                           int(e.duration_ns)) for e in line.events]
        if lines["ops"] or lines["modules"]:
            out[plane.name] = lines
    return out


def _short(name: str) -> str:
    """An operation's name without its HLO text: ``%fusion.3 = ...``."""
    return name.split(" = ", 1)[0]


def start(trace_dir: str) -> None:
    """Trace the device, without Python's call tracing (which would
    make the file hundreds of MB for a few steps)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def _union(intervals, lo, hi) -> List[Tuple[int, int]]:
    """Merged, clipped [start, end) intervals."""
    merged: List[List[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _base(name: str) -> str:
    """Program name without the run id XLA appends: ``jit_step(12)``."""
    return name.split("(")[0]


def reduce(devices: Dict[str, Dict[str, List[Event]]], window: Tuple[int, int],
           top: int = 10) -> dict:
    """Numbers of one traced window ``[start_ns, end_ns)``.

    Returns ``busy_s`` and ``window_s`` (busy averaged over devices),
    ``step_program``, ``step_count`` and ``step_device_s`` (per device,
    averaged), ``step_ms`` (mean execution), and ``device_ops`` /
    ``idle_gaps`` lists for ``breakdown``.  ``busy_s`` is 0 where no
    operation ran.
    """
    lo, hi = window
    n = max(len(devices), 1)
    busy = 0
    op_time: Dict[str, float] = {}
    mod_time: Dict[str, float] = {}
    mod_runs: Dict[str, int] = {}
    gaps: List[Tuple[str, float]] = []
    for lines in devices.values():
        ops = lines["ops"] or lines["modules"]
        spans = _union([(s, s + d) for _, s, d in ops], lo, hi)
        busy += sum(e - s for s, e in spans)
        for name, s, d in lines["ops"]:
            if lo <= s < hi:
                op_time[name] = op_time.get(name, 0.0) + d
        mods = sorted((s, s + d, _base(name))
                      for name, s, d in lines["modules"] if lo <= s < hi)
        for s, e, name in mods:
            mod_time[name] = mod_time.get(name, 0.0) + (e - s)
            mod_runs[name] = mod_runs.get(name, 0) + 1
        starts = [m[0] for m in mods]
        for (_, e0), (s1, _) in zip(spans, spans[1:]):
            k = bisect.bisect_left(starts, s1) - 1
            label = (f"after {mods[k][2]}" if k >= 0
                     else "before any program")
            gaps.append((label, (s1 - e0) / 1e9))
    step = max(mod_time, key=mod_time.get) if mod_time else None
    out = {
        "busy_s": busy / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "step_program": step,
        "step_count": mod_runs.get(step, 0) / n if step else 0,
        "step_device_s": mod_time.get(step, 0.0) / n / 1e9 if step else 0.0,
        "device_ops": [[k, v / n / 1e9] for k, v in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v] for k, v in
                      sorted(gaps, key=lambda kv: -kv[1])[:top]],
    }
    out["step_ms"] = (1e3 * out["step_device_s"] / out["step_count"]
                      if out["step_count"] else None)
    return out


def window(devices: Dict[str, Dict[str, List[Event]]]) -> Tuple[int, int]:
    """The traced window on the trace's own clock: from the first device
    event to the end of the last one."""
    spans = [(s, s + d) for lines in devices.values()
             for key in ("ops", "modules") for _, s, d in lines[key]]
    if not spans:
        return (0, 1)
    return (min(s for s, _ in spans), max(e for _, e in spans))
