"""One run of one cell: set-up, the measured window, the check.

``run_cell`` is everything ``run.py`` does after parsing its arguments.
It finds the cell's configuration, traffic and metric readers by the
names in ``BENCHMARK.json``; nothing here names a cell.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import sys
import tempfile
import time
from pathlib import Path
from typing import Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
DRAIN_S = 60.0          # wait this long past the close for first tokens
SAMPLE_TOKENS = 512     # served tokens the check compares, at least
SAMPLE_MAX = 8          # requests the check compares, at most
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def _log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


# -- finding a cell's files by name -------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic_name: str
    traffic: dict
    chips: int
    end_to_end: list
    per_layer: list
    limits: dict


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def resolve(bench: dict, workload: str, root: Path = ROOT) -> Cell:
    from bench import traffic

    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / entry["file"]).read_text())
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    moved = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"]
           if (workload in m["workloads"] if "workloads" in m
               else m["moves"] in moved)]
    limits = json.loads((BENCH / "limits" / f"{w['config']}.json").read_text())
    return Cell(workload, config, w["traffic"], traffic.load(w["traffic"]),
                w["chips"], e2e, per, limits)


def reader(metric: str):
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def dims_of(config: dict) -> dict:
    """The sizes the weights, the reference and the counts need, read
    from the configuration's own (Hugging Face) keys."""
    return {"layers": config["num_hidden_layers"],
            "d_model": config["hidden_size"],
            "n_heads": config["num_attention_heads"],
            "n_kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "d_ff": config["intermediate_size"],
            "vocab": config["vocab_size"],
            "tied": config["tie_word_embeddings"],
            "norm_eps": config["rms_norm_eps"],
            "rope_theta": float(config["rope_theta"])}


def model_config(config: dict):
    """The program's ModelConfig for this file: its registry entry for
    ``arch`` with every size the file states (the file is the
    configuration as it is run)."""
    from repro.configs import get_config

    d = dims_of(config)
    cfg = dataclasses.replace(
        get_config(config["arch"]), n_layers=d["layers"],
        d_model=d["d_model"], n_heads=d["n_heads"],
        n_kv_heads=d["n_kv_heads"], head_dim=d["head_dim"], d_ff=d["d_ff"],
        vocab_size=d["vocab"], tie_embeddings=d["tied"],
        norm_eps=d["norm_eps"], rope_theta=d["rope_theta"],
        dtype=config["torch_dtype"])
    if cfg.family != "dense" or cfg.activation != "silu_glu" \
            or not cfg.qk_norm or cfg.moe is not None:
        raise ValueError(f"{config['arch']} is not a Qwen3 dense stack")
    return cfg


def check_devices(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoChip(f"cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


# -- statistics ---------------------------------------------------------------
def percentile(values, q: float) -> Optional[float]:
    """Nearest-rank percentile (a value that was measured)."""
    if not len(values):
        return None
    v = sorted(values)
    return float(v[max(0, math.ceil(q / 100.0 * len(v)) - 1)])


# -- the run ------------------------------------------------------------------
def _weights(cfg, dims: dict, seed: int, tp: int, devices):
    import jax

    from bench import weights
    from repro.models import lm

    shape = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
    weights.check_layout(shape, dims)
    shardings = None
    if tp > 1:
        from repro import compat
        from repro.parallel import sharding as rules

        mesh = compat.make_mesh((1, tp), ("data", "model"),
                                devices=devices[:tp])
        shardings = rules.named(rules.serve_param_specs(shape, mesh, cfg),
                                mesh)
    params = weights.make(dims, seed, shardings)
    jax.block_until_ready(params)
    return params


def _work(client, lo: float, hi: float, prefill_tokens: int) -> list:
    """Spans ``(start, stop, head)`` of the tokens the steps processed in
    ``[lo, hi)``: each decode token at its own position, and the prompts
    whose first token came in the window, apportioned so that their
    total is the engine's count of prompt tokens prefilled (the cache
    served the rest, from the front)."""
    spans, prompts = [], []
    for r in client.recs:
        st = client.stamps.get(r.rid, [])
        L = len(r.prompt)
        for k, (t, _, _) in enumerate(st):
            if lo <= t < hi:
                if k == 0:
                    prompts.append(L)
                else:
                    spans.append((L + k - 1, L + k, True))
    scale = min(1.0, prefill_tokens / sum(prompts)) if prompts else 0.0
    for L in prompts:
        n = int(round(L * scale))
        spans.append((L - n, L, True))
    return spans


def latencies(client, w0: float, w1: float, deadline: float):
    """End-to-end numbers of the window ``[w0, w1)``, from the client's
    stamps.  TTFT of each request due in the window, from its due time;
    one that got no first token by ``deadline`` (or was refused) is
    failed and counts as at least that late.  Every gap between two
    tokens of a request that ends in the window.  Every token delivered
    in the window."""
    due = [r for r in client.recs if w0 <= r.due < w1]
    ttft, failed = [], 0
    for r in due:
        st = client.stamps.get(r.rid) if r.refused is None else None
        if st and st[0][0] <= deadline:
            ttft.append(1e3 * (st[0][0] - r.due))
        else:
            failed += 1
            ttft.append(1e3 * (deadline - r.due))
    gaps, tokens = [], 0
    for r in client.recs:
        st = client.stamps.get(r.rid, [])
        for (a, _, _), (b, _, _) in zip(st, st[1:]):
            if w0 <= b < w1:
                gaps.append(1e3 * (b - a))
        tokens += sum(1 for t, _, _ in st if w0 <= t < w1)
    late = [1e3 * (r.sent - r.due) for r in due]
    values = {"ttft_p95_ms": percentile(ttft, 95),
              "ttft_p90_ms": percentile(ttft, 90),
              "ttft_p75_ms": percentile(ttft, 75),
              "itl_p95_ms": percentile(gaps, 95),
              "tokens_per_s": tokens / (w1 - w0)}
    seen = {"due": due, "attempted": len(due), "failed": failed,
            "ttft_p50_ms": percentile(ttft, 50),
            "ttft_p75_ms": values["ttft_p75_ms"],
            "ttft_p90_ms": values["ttft_p90_ms"],
            "ttft_p95_ms": values["ttft_p95_ms"],
            "itl_p50_ms": percentile(gaps, 50), "itl_samples": len(gaps),
            "tokens": tokens, "sender_late_p95_ms": percentile(late, 95)}
    return values, seen


@dataclasses.dataclass
class Served:
    """The system under test, built for one cell and seed."""
    cell: Cell
    devices: list
    cfg: object
    dims: dict
    params: dict
    llm: object
    compiles: dict


def build(cell: Cell, seed: int, require_tpu: bool = True,
          fault=None) -> Served:
    """Weights on the device from the seed, and ``LLM`` over them."""
    import jax

    from repro.launch import compile_cache

    if require_tpu:
        compile_cache.enable()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = {"n": 0, "window": False, "in_window": 0, "in_window_s": 0.0,
                "loaded_in_window": 0}

    def on_duration(event, secs, **_):
        if event == COMPILE_EVENT:
            compiles["n"] += 1
            if compiles["window"]:
                compiles["in_window"] += 1
                compiles["in_window_s"] += secs

    def on_event(event, **_):
        if event == CACHE_HIT_EVENT and compiles["window"]:
            compiles["loaded_in_window"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    tp = int(cell.config.get("tp", 1))
    chips = max(cell.chips, tp)
    devices = (check_devices(chips) if require_tpu
               else jax.devices()[:chips])
    cfg = model_config(cell.config)
    dims = dims_of(cell.config)
    params = _weights(cfg, dims, seed, tp, devices)

    from repro.serve.api import LLM

    engine_kw = dict(cell.config["engine"])
    if tp > 1:
        engine_kw["tp"] = tp
    llm = LLM(params, cfg, **engine_kw)
    if fault is not None:
        fault(llm)
    return Served(cell, devices, cfg, dims, params, llm, compiles)


def measure(sv: Served, schedule, seconds: float, trace_dir=None) -> dict:
    """Send ``schedule`` (pre-roll, then the window of ``seconds``) while
    advancing the engine, keep advancing it until what was due has its
    first token, and cancel what is left.  Returns the client and the
    window's stamps."""
    import jax

    from bench.client import Client
    from repro.serve.params import SamplingParams

    llm, compiles = sv.llm, sv.compiles
    if sv.cell.traffic.get("warm"):
        from bench import warmup

        t = time.perf_counter()
        n = warmup.warm(llm, sv.cell.traffic["warm"], sv.cell.config["engine"],
                        sv.dims["vocab"])
        _log(f"warm-up: {n} steps in {time.perf_counter() - t:.1f}s")
    client = Client(llm, lambda n: SamplingParams(max_new_tokens=n))
    counters = {}

    def open_window(state):
        counters["start"] = dict(llm.stats)
        compiles["window"] = True
        state["sample_kv"] = True
        if trace_dir:
            from bench import trace

            trace.start(trace_dir)

    def close_window(state):
        if trace_dir:
            jax.profiler.stop_trace()
        counters["end"] = dict(llm.stats)
        compiles["window"] = False
        state["sample_kv"] = False

    preroll = float(sv.cell.traffic.get("preroll_s", 0.0))
    t0 = time.perf_counter()
    client.run(schedule, t0, t0 + preroll + seconds,
               hooks=[(t0 + preroll, open_window),
                      (t0 + preroll + seconds, close_window)])
    # the window is the scheduled one (due times are scheduled); the
    # counters and the trace open at the first step boundary after it
    w0, w1 = t0 + preroll, t0 + preroll + seconds
    client.drain(w0, w1, w1 + DRAIN_S)
    queued = llm.stats["queue_depth"]
    for r in client.recs:
        if r.req is not None and not r.req.done:
            llm.engine.cancel(r.req)
    llm.engine.remove_consumer(client.on_chunk)
    return {"client": client, "w0": w0, "w1": w1, "deadline": w1 + DRAIN_S,
            "counters": counters, "queued_after_drain": queued}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, root: Path = ROOT,
             config_override: Optional[dict] = None,
             traffic_override: Optional[dict] = None,
             limits_override: Optional[dict] = None,
             t_start: Optional[float] = None, fault=None,
             control: bool = False) -> dict:
    """Run one cell and return the result line (a dict).

    The overrides and ``fault`` serve the tests: a smaller configuration
    and traffic on the CPU, and a planted fault that the check must see.
    ``control`` puts the lower-precision control in the program's place
    in the check (``control.py``; the benchmark's own runs never do).
    """
    t_start = time.perf_counter() if t_start is None else t_start
    cell = resolve(load_benchmark(root), workload, root)
    cell.config = {**cell.config, **(config_override or {})}
    cell.traffic = {**cell.traffic, **(traffic_override or {})}
    cell.limits = {**cell.limits, **(limits_override or {})}

    from bench import traffic

    sv = build(cell, seed, require_tpu, fault)
    schedule = traffic.generate(cell.traffic, seed, seconds,
                                sv.dims["vocab"])
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    m = measure(sv, schedule, seconds, trace_dir)
    client, w0, w1, deadline = m["client"], m["w0"], m["w1"], m["deadline"]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in sv.devices)

    e2e, seen = latencies(client, w0, w1, deadline)
    e2e["setup_s"] = w0 - t_start
    c = sv.compiles
    _log("window_s", round(w1 - w0, 6), " ".join(
        f"{k} {v}" for k, v in seen.items() if k != "due"),
        f"compiles_in_window {c['in_window']} ({c['in_window_s']:.3f}s) "
        f"programs_loaded_in_window {c['loaded_in_window']} "
        f"compiles_total {c['n']} "
        f"queued_after_drain {m['queued_after_drain']}")

    counters = m["counters"]
    run = {"counters": counters,
           "kv": {"samples": client.kv_samples,
                  "num_blocks": sv.llm.kv_usage()["num_blocks"]},
           "dims": sv.dims, "chips": cell.chips, "trace": None,
           "work": _work(client, w0, w1,
                         counters["end"]["prefill_tokens"]
                         - counters["start"]["prefill_tokens"])}
    device_info, breakdown = {}, None
    if trace:
        import shutil

        from bench import peaks as peak_table
        from bench import trace as tr

        run["peaks"] = peak_table.peaks(sv.devices[0].device_kind)
        events = tr.load(tr.find_xplane(trace_dir))
        red = tr.reduce(events, tr.window(events))
        shutil.rmtree(trace_dir, ignore_errors=True)
        run["trace"] = red
        device_info = {"busy_s": red["busy_s"], "window_s": red["window_s"]}
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}

    # -- the program's state is freed before the reference runs -------------
    samples = [(r.prompt, client.generated(r)) for r in client.recs
               if r.req is not None and r.req.finish_reason == "length"
               and w0 <= client.finished(r) <= deadline]
    params, dims, devices = sv.params, sv.dims, sv.devices
    del sv, m, client
    gc.collect()
    check = _check(params, dims, samples, seed, cell.limits, control)

    if trace:
        metrics = {}
        for mt in cell.per_layer:
            v = reader(mt["name"])(run)
            if v is not None:
                metrics[mt["name"]] = {"value": v, "unit": mt["unit"]}
    else:
        metrics = {mt["name"]: {"value": e2e[mt["name"]], "unit": mt["unit"]}
                   for mt in cell.end_to_end}
    d0 = devices[0]
    result = {"correct": check["correct"], "attempted": seen["attempted"],
              "failed": seen["failed"], "metrics": metrics,
              "device": {"platform": d0.platform, "kind": d0.device_kind,
                         "count": len(devices), "memory_peak_bytes": peak,
                         **device_info}}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control:
        result["program_served_gap"] = check["program_gap"]
    result["checked"] = check["numbers"]
    for name, num in check["numbers"].items():
        _log(f"checked {name} {num['value']} limit {num['limit']}")
    return result


def _check(params, dims, samples, seed: int, limits: dict,
           control: bool = False) -> dict:
    """Compare a seeded sample of finished requests, the longest among
    them, with the reference: the widest gap by which a served token's
    reference logit lies below the reference's best.

    With ``control`` the lower-precision reference stands in for the
    program: at each position of the same prompts and served tokens, the
    token that its forward puts first is judged in place of the served
    one, and ``correct`` follows from that.  The program's own reading is
    returned beside it (``program_gap``)."""
    from bench import reference

    limit = limits["served_gap"]
    if not samples:
        _log("check: no request finished in the window")
        return {"correct": False, "program_gap": None,
                "numbers": {"served_gap": {"value": None, "limit": limit}}}
    rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), 11])
    longest = max(range(len(samples)),
                  key=lambda i: len(samples[i][0]) + len(samples[i][1]))
    pick, served = [], 0
    for i in [longest] + [int(i) for i in rng.permutation(len(samples))
                          if i != longest]:
        if served >= SAMPLE_TOKENS or len(pick) >= SAMPLE_MAX:
            break
        pick.append(i)
        served += len(samples[i][1])
    worst, ctl = 0.0, 0.0
    t = time.perf_counter()
    for i in pick:
        prompt, gen = samples[i]
        worst = max(worst, float(np.max(
            reference.served_gaps(params, dims, prompt, gen))))
        if control:
            ctl = max(ctl, float(np.max(
                reference.control_gaps(params, dims, prompt, gen))))
    _log(f"check compared {len(pick)} requests, {served} served tokens "
         f"in {time.perf_counter() - t:.1f}s")
    if control:
        _log(f"program served_gap {worst}; the fp8 control stands in")
    value = ctl if control else worst
    return {"correct": bool(value <= limit), "program_gap": worst,
            "numbers": {"served_gap": {"value": value, "limit": limit}}}
