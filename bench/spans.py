"""Profiler trace -> device time by scope, idle time by host span.

A companion of ``trace.py`` over the same ``.xplane.pb``.  ``load``
keeps, beside each device operation, the scope of the step program it
ran under (the innermost of ``SCOPES`` on the operation's name path,
which ``jax.named_scope`` writes into the op metadata), and from the
host planes the spans the program opens (``serve.*``), those a client
or harness opens (``client.*``, ``host.gc``) and JAX's compiles.
``reduce`` takes plain tuples, so it is tested without a chip:

* ``scopes``: device seconds of the step program's leaf operations by
  scope, and the unscoped rest by operation.  Container operations
  (``while``, ``call``, ``conditional``) are left out: their time is
  that of the operations inside them, which the trace lists too;
* ``idle``: the device's idle seconds by the innermost host span that
  covered each instant of them; an instant no span covers is named by
  the program that ran before it (``after <program>``, as ``trace.py``
  does).  Each gap is labelled by the name that covers most of it;
* ``clock``: the share of step executions that start after the
  ``serve.dispatch`` span that launched them begins and end before the
  ``serve.sync`` span after it ends, which holds only if host and
  device events share one clock; ``clock_skew_ms``, the range of
  constant offsets of the device's clock that would make it hold for
  every execution;
* ``engine_host_ms``: the mean of ``serve.step`` less its
  ``serve.sync``;
* ``device_ops``: the operations with the most device time, each named
  ``<scope>/<operation>``.

    python bench/spans.py <trace dir or .xplane.pb>

prints the reduction of a trace, as one JSON line, and

    python bench/spans.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> --out <dir>

runs one window of a cell as ``run.py`` does, without the check, keeps
its trace under ``<dir>`` and prints the window's steps, ITL median and
the reduction.  It opens the client's spans (``client.send``,
``client.sample_kv``) around the benchmark client's methods and a
``host.gc`` span around each collection of the garbage collector.
"""
from __future__ import annotations

import bisect
import json
import re
import sys
from typing import Dict, List, Optional, Tuple

# the scopes of the serving step (models/layers.py, serve/engine.py,
# models/api.py); a changed name changes what every metric below reads
SCOPES = ("qkv_proj", "kv_write", "attention", "out_proj", "mlp", "head",
          "verify")
HOST_PREFIXES = ("serve.", "client.", "host.gc")
COMPILES = ("backend_compile", "backend_compile_and_load")
CONTAINER = re.compile(r"%?(while|call|conditional)(\.\d+)?$")

Op = Tuple[str, int, int, Optional[str]]       # (name, start, dur, scope)
Event = Tuple[str, int, int]                   # (name, start, dur)
Span = Tuple[str, int, int]                    # (name, start, end)


def scope_of(path: str) -> Optional[str]:
    """The innermost of ``SCOPES`` among the components of an op's name
    path (``jit(step)/while/body/attention/dot_general``), or None."""
    for part in reversed(path.split("/")):
        if part in SCOPES:
            return part
    return None


def _base(name: str) -> str:
    return name.split("(")[0]


def _union(intervals, lo, hi) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _innermost(active: List[Span], lo: int, hi: int) -> Dict[str, int]:
    """Nanoseconds of ``[lo, hi)`` by the innermost of ``active`` (the
    latest-starting span covering each instant, the shorter on a tie)."""
    cuts = sorted({lo, hi} | {t for _, s, e in active for t in (s, e)
                              if lo < t < hi})
    out: Dict[str, int] = {}
    for a, b in zip(cuts, cuts[1:]):
        cover = [sp for sp in active if sp[1] <= a and b <= sp[2]]
        if cover:
            name = max(cover, key=lambda sp: (sp[1], -sp[2]))[0]
            out[name] = out.get(name, 0) + (b - a)
    return out


def _idle(gaps: List[Tuple[int, int, str]], host: List[Span]):
    """Label each gap ``(start, end, fallback)`` by the host spans; returns
    ``(seconds by name, [(label, seconds)] per gap)``."""
    spans = sorted(host, key=lambda sp: sp[1])
    by_name: Dict[str, float] = {}
    labelled = []
    nxt, active = 0, []
    for g0, g1, fallback in sorted(gaps):
        while nxt < len(spans) and spans[nxt][1] < g1:
            active.append(spans[nxt])
            nxt += 1
        active = [sp for sp in active if sp[2] > g0]
        cover = _innermost(active, g0, g1)
        rest = (g1 - g0) - sum(cover.values())
        if rest:
            cover[fallback] = cover.get(fallback, 0) + rest
        for name, ns in cover.items():
            by_name[name] = by_name.get(name, 0.0) + ns / 1e9
        labelled.append((max(cover, key=cover.get), (g1 - g0) / 1e9))
    return by_name, labelled


def _clock(steps: List[Tuple[int, int]], host: List[Span]):
    """``(share, skew)`` for step executions ``(start, end)``.  ``share``:
    those that begin after the latest ``serve.dispatch`` begun before
    them and end before the first ``serve.sync`` that ends after that
    dispatch began.  ``skew``: ``[lo, hi]`` ms, the constant shifts of
    the device's events that would put every execution inside the
    dispatch-to-sync span of the step nearest it (None if no shift
    does, or nothing was traced)."""
    disp = sorted(s for name, s, _ in host if name == "serve.dispatch")
    sync_ends = sorted(e for name, _, e in host if name == "serve.sync")
    if not steps or not disp:
        return None, None
    ok, lo, hi = 0, -float("inf"), float("inf")
    for s, e in steps:
        k = bisect.bisect_right(disp, s) - 1
        if k >= 0:
            j = bisect.bisect_right(sync_ends, disp[k])
            ok += j < len(sync_ends) and e <= sync_ends[j]
        # the dispatch nearest the execution's start, either side of it
        k = min((k, k + 1), key=lambda i: abs(disp[i] - s)
                if 0 <= i < len(disp) else float("inf"))
        j = bisect.bisect_right(sync_ends, disp[k])
        if j == len(sync_ends):
            return ok / len(steps), None
        lo, hi = max(lo, disp[k] - s), min(hi, sync_ends[j] - e)
    return ok / len(steps), ([lo / 1e6, hi / 1e6] if lo <= hi else None)


def _host_ms(host: List[Span]) -> Optional[float]:
    """Mean of each ``serve.step`` less the ``serve.sync`` inside it."""
    steps = sorted((s, e) for name, s, e in host if name == "serve.step")
    syncs = sorted((s, e) for name, s, e in host if name == "serve.sync")
    if not steps:
        return None
    starts = [s for s, _ in syncs]
    total = 0
    for s, e in steps:
        k = bisect.bisect_left(starts, s)
        waited = 0
        while k < len(syncs) and syncs[k][1] <= e:
            waited += syncs[k][1] - syncs[k][0]
            k += 1
        total += (e - s) - waited
    return total / len(steps) / 1e6


def reduce(devices: Dict[str, Dict[str, list]], host: List[Span],
           window: Tuple[int, int], top: int = 10) -> dict:
    """Numbers of one traced window ``[start_ns, end_ns)``.

    ``devices``: ``{plane: {"ops": [Op], "modules": [Event]}}``;
    ``host``: the kept host spans.  Device seconds are averaged over the
    devices, as in ``trace.reduce``."""
    lo, hi = window
    n = max(len(devices), 1)
    host = [sp for sp in host if sp[2] > lo and sp[1] < hi]
    mod_time: Dict[str, int] = {}
    for lines in devices.values():
        for name, s, d in lines["modules"]:
            if lo <= s < hi:
                mod_time[_base(name)] = mod_time.get(_base(name), 0) + d
    step = max(mod_time, key=mod_time.get) if mod_time else None
    scopes: Dict[Optional[str], float] = {}
    unscoped: Dict[str, float] = {}
    op_time: Dict[str, float] = {}
    gaps: List[Tuple[int, int, str]] = []
    runs: List[Tuple[int, int]] = []
    for lines in devices.values():
        mods = sorted((s, s + d, _base(name))
                      for name, s, d in lines["modules"] if lo <= s < hi)
        mine = [(s, e) for s, e, name in mods if name == step]
        runs += mine
        starts = [s for s, _ in mine]
        for name, s, d, scope in lines["ops"]:
            if not lo <= s < hi:
                continue
            label = f"{scope}/{name}" if scope else name
            op_time[label] = op_time.get(label, 0.0) + d
            k = bisect.bisect_right(starts, s) - 1
            if CONTAINER.match(name) or k < 0 or s >= mine[k][1]:
                continue
            scopes[scope] = scopes.get(scope, 0.0) + d
            if scope is None:
                unscoped[name] = unscoped.get(name, 0.0) + d
        busy = _union([(s, s + d) for _, s, d, _ in lines["ops"]]
                      or [(s, e) for s, e, _ in mods], lo, hi)
        mstarts = [m[0] for m in mods]
        for (_, e0), (s1, _) in zip(busy, busy[1:]):
            k = bisect.bisect_left(mstarts, s1) - 1
            gaps.append((e0, s1, f"after {mods[k][2]}" if k >= 0
                         else "before any program"))
    idle, labelled = _idle(gaps, host)
    clock, skew = _clock(sorted(runs), host)
    leaf = sum(scopes.values())
    named = sum(v for k, v in idle.items() if k.startswith(HOST_PREFIXES)
                or k in COMPILES)

    def ranked(d, k=top):
        return [[name, v / n / 1e9] for name, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:k]]

    return {
        "step_program": step,
        "scopes": {k: v / n / 1e9 for k, v in scopes.items()
                   if k is not None},
        "step_leaf_s": leaf / n / 1e9,
        "scoped_share": (leaf - scopes.get(None, 0.0)) / leaf
        if leaf else None,
        "unscoped_ops": ranked(unscoped),
        "device_ops": ranked(op_time),
        "idle": {k: v / n for k, v in sorted(idle.items(),
                                             key=lambda kv: -kv[1])},
        "idle_s": sum(idle.values()) / n,
        "idle_named_share": named / sum(idle.values()) if idle else None,
        "idle_gaps": [[k, v] for k, v in
                      sorted(labelled, key=lambda kv: -kv[1])[:top]],
        "clock": clock,
        "clock_skew_ms": skew,
        "step_runs": len(runs) / n,
        "engine_host_ms": _host_ms(host),
    }


# -- reading a trace ----------------------------------------------------------
def load(path: str):
    """``(devices, host)`` of one trace: each device plane's operations
    (with the scope of their ``tf_op`` name path) and program
    executions, and the kept host spans (``xplane.py`` reads the op
    metadata that ``jax.profiler.ProfileData`` leaves out)."""
    from bench import trace, xplane

    def keep(plane, line):
        return (plane.startswith("/host:")
                or plane.startswith("/device:") and "CPU" not in plane
                and line in (trace.OPS_LINE, trace.MODULES_LINE))

    devices, host = {}, []
    for e in xplane.events(path, keep):
        if e.plane.startswith("/host:"):
            if e.name.startswith(HOST_PREFIXES) or e.name in COMPILES:
                host.append((e.name, e.start_ns, e.end_ns))
            continue
        lines = devices.setdefault(e.plane, {"ops": [], "modules": []})
        if e.line == trace.OPS_LINE:
            path_ = str(e.meta.get("tf_op", "")).rstrip(":")
            lines["ops"].append((trace._short(e.name), e.start_ns,
                                 e.end_ns - e.start_ns, scope_of(path_)))
        else:
            lines["modules"].append((e.name, e.start_ns,
                                     e.end_ns - e.start_ns))
    return devices, host


def reduce_file(path: str) -> dict:
    from bench import trace

    if not path.endswith(".xplane.pb"):
        path = trace.find_xplane(path)
    devices, host = load(path)
    return reduce(devices, host, trace.window(
        {k: {"ops": [o[:3] for o in v["ops"]], "modules": v["modules"]}
         for k, v in devices.items()}))


# -- one window of a cell, its trace kept -------------------------------------
def _open_client_spans():
    """Spans around the benchmark client's sending and KV sampling, and
    a ``host.gc`` span around every collection; returns the undo."""
    import gc

    import jax

    from bench.client import Client

    saved = {m: getattr(Client, m) for m in ("send", "_sample_kv")}
    Client.send = jax.profiler.annotate_function(saved["send"],
                                                 name="client.send")
    Client._sample_kv = jax.profiler.annotate_function(
        saved["_sample_kv"], name="client.sample_kv")
    open_gc = []

    def on_gc(phase, info):
        if phase == "start":
            span = jax.profiler.TraceAnnotation("host.gc")
            span.__enter__()
            open_gc.append(span)
        elif open_gc:
            open_gc.pop().__exit__(None, None, None)

    gc.callbacks.append(on_gc)

    def undo():
        gc.callbacks.remove(on_gc)
        for m, fn in saved.items():
            setattr(Client, m, fn)
    return undo


def capture(workload: str, seed: int, seconds: float, traced: bool,
            out: str, *, require_tpu: bool = True,
            config_override: Optional[dict] = None,
            traffic_override: Optional[dict] = None) -> dict:
    """One window of ``workload``; the overrides serve the tests, as in
    ``harness.run_cell``."""
    import os
    import shutil

    from bench import harness, traffic
    from bench import trace as tr

    cell = harness.resolve(harness.load_benchmark(), workload)
    cell.config = {**cell.config, **(config_override or {})}
    cell.traffic = {**cell.traffic, **(traffic_override or {})}
    sv = harness.build(cell, seed, require_tpu)
    schedule = traffic.generate(cell.traffic, seed, seconds, sv.dims["vocab"])
    trace_dir = os.path.join(out, f"trace-{workload}-{seed}") if traced \
        else None
    undo = _open_client_spans() if traced else (lambda: None)
    try:
        m = harness.measure(sv, schedule, seconds, trace_dir)
    finally:
        undo()
    _, seen = harness.latencies(m["client"], m["w0"], m["w1"],
                                m["deadline"])
    run = {"counters": m["counters"]}
    start, end = m["counters"]["start"], m["counters"]["end"]
    result = {"workload": workload, "seed": seed, "traced": traced,
              "steps": end["iterations"] - start["iterations"],
              "itl_p50_ms": seen["itl_p50_ms"],
              "itl_samples": seen["itl_samples"],
              "ttft_p75_ms": seen["ttft_p75_ms"],
              "step_fill_share": harness.reader("step_fill_share")(run),
              "engine_host_ms": harness.reader("engine_host_ms")(run)}
    if traced:
        path = tr.find_xplane(trace_dir)
        events = tr.load(path)
        red = tr.reduce(events, tr.window(events))
        result["trace"] = {k: red[k] for k in
                           ("busy_s", "window_s", "step_count", "step_ms")}
        result["spans"] = reduce_file(path)
        shutil.copy(path, os.path.join(out, f"{workload}-{seed}.xplane.pb"))
        shutil.rmtree(trace_dir, ignore_errors=True)
    return result


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", nargs="?")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out", help="where a capture keeps its trace")
    args = ap.parse_args(argv)
    if not args.path and not args.out:
        ap.error("a capture needs --out")
    if args.path:
        print(json.dumps(reduce_file(args.path)))
    else:
        print(json.dumps(capture(args.workload, args.seed, args.seconds,
                                 bool(args.trace), args.out)), flush=True)
    return 0


if __name__ == "__main__":
    from pathlib import Path

    ROOT = Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    sys.exit(main())
