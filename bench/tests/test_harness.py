"""Rehearsal of the harness without a chip: names resolve, traffic is
deterministic, latency is timed from the due time, no chip means no
result."""
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import harness, traffic
from bench.client import Client, Rec

BENCH = harness.load_benchmark()


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_workload_resolves_by_name(workload):
    cell = harness.resolve(BENCH, workload)
    harness.model_config(cell.config)          # sizes match the program
    assert cell.chips == cell.config["chips"]
    assert "served_gap" in cell.limits
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in names
        assert callable(harness.reader(m["name"]))


def test_every_file_of_a_config_is_named():
    for c in BENCH["configs"]:
        conf = json.loads((harness.ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])


CLOSED = {"kind": "closed", "clients": 32, "requests": 256,
          "prompt": {"dist": "lognormal", "median": 1024, "sigma": 0.5,
                     "min": 256, "max": 2048},
          "output": {"dist": "uniform", "min": 256, "max": 512}}


SESSIONS = {"kind": "sessions", "session_rate_per_s": 0.4, "preroll_s": 15,
            "system_prompts": {"count": 8, "length": 1536, "zipf_s": 1.1},
            "turns": {"min": 2, "max": 6}, "user": {"min": 32, "max": 192},
            "answer": {"min": 32, "max": 192}, "think_s_mean": 2.0}


@pytest.mark.parametrize("name", ["chat", "sessions", "closed"])
def test_traffic_is_the_same_work_in_another_order(name):
    spec = {"sessions": SESSIONS, "closed": CLOSED}.get(name) \
        or traffic.load(name)
    a = traffic.generate(spec, 2 ** 40 + 1, 30, 151936)
    b = traffic.generate(spec, 2 ** 40 + 1, 30, 151936)
    c = traffic.generate(spec, 7, 30, 151936)

    def sizes(s):
        if s.kind == "sessions":
            return sorted((len(s.systems[i.system]), len(t.user),
                           t.answer_len) for i in s.items for t in i.turns)
        return sorted((len(i.prompt), i.max_new) for i in s.items)

    def order(s):
        return [(i.due_s, len(i.prompt) if i.prompt is not None else
                 i.system) for i in s.items]

    assert order(a) == order(b)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in
               zip(a.items, b.items) if x.prompt is not None)
    assert order(a) != order(c)
    assert sizes(a) == sizes(c)


def test_stratified_draws_follow_the_distribution():
    q = traffic.quantiles({"dist": "lognormal", "median": 512, "sigma": 1.0,
                           "min": 32, "max": 2048}, 1001)
    assert q[500] == 512 and q.min() >= 32 and q.max() == 2048
    picks = traffic.zipf_picks(8, 1.1, 1000)
    counts = np.bincount(picks, minlength=8)
    assert list(counts) == sorted(counts, reverse=True) and counts[0] > 300


class _Engine:
    max_len = 4096
    has_work = False          # it never serves: nothing ever completes

    def __init__(self):
        self.consumers = []

    def add_consumer(self, fn):
        self.consumers.append(fn)


class _LLM:
    """Accepts requests; the test delivers their tokens by hand."""

    def __init__(self):
        self.engine = _Engine()
        self.sent = []

    def submit(self, prompt, params):
        rec = type("Req", (), {"rid": len(self.sent), "done": False})()
        self.sent.append(rec)
        return rec

    def kv_usage(self):
        return {"blocks_in_use": 3, "num_blocks": 10}


def test_ttft_is_timed_from_the_due_time():
    llm = _LLM()
    client = Client(llm, lambda n: None)
    # due at 100 s, sent 0.5 s late, first token 0.75 s after the send
    rec = client.send(Rec(due=100.0, prompt=np.zeros(4, np.int32),
                          max_new=2))
    rec.sent = 100.5
    client.stamps[rec.rid] = [(101.25, 7, None), (101.5, 8, "length")]
    values, seen = harness.latencies(client, 99.0, 102.0, 200.0)
    assert values["ttft_p75_ms"] == pytest.approx(1250.0)
    assert values["ttft_p95_ms"] == pytest.approx(1250.0)
    assert seen["sender_late_p95_ms"] == pytest.approx(500.0)
    assert values["itl_p95_ms"] == pytest.approx(250.0)
    assert values["tokens_per_s"] == pytest.approx(2 / 3.0)


def test_a_request_without_a_first_token_is_failed():
    client = Client(_LLM(), lambda n: None)
    client.send(Rec(due=10.0, prompt=np.zeros(4, np.int32), max_new=2))
    values, seen = harness.latencies(client, 9.0, 11.0, 70.0)
    assert seen["failed"] == 1 and values["ttft_p75_ms"] == 60_000.0


def test_open_loop_sends_on_schedule_not_on_completion():
    llm = _LLM()
    client = Client(llm, lambda n: None)
    spec = {"kind": "open", "rate_per_s": 50.0,
            "prompt": {"dist": "uniform", "min": 4, "max": 8},
            "output": {"dist": "uniform", "min": 2, "max": 2}}
    sched = traffic.generate(spec, 3, 0.2, 100)
    import time
    t0 = time.perf_counter()
    client.run(sched, t0, t0 + 0.3)
    # nothing ever completes, yet every request due before the stop went
    assert len(llm.sent) == len(sched.items) == 10


def test_run_without_a_chip_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         "qwen3-0.6b.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_run_outside_a_checkout_fails(tmp_path):
    """Only BENCHMARK.json and the benchmark's files: no program to run."""
    import shutil

    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-0.6b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_every_window_holds_the_same_requests():
    """The pre-roll and the window draw their own sets, so every seed's
    window holds the same sizes, all due inside it."""
    spec = traffic.load("chat")
    pre, seconds = spec["preroll_s"], 51
    windows = []
    for seed in (2 ** 40 + 5, 11):
        s = traffic.generate(spec, seed, seconds, 151936)
        win = [i for i in s.items if i.due_s >= pre]
        assert all(i.due_s < pre + seconds for i in win)
        assert len(s.items) - len(win) == math.ceil(
            spec["preroll_rate_per_s"] * pre)
        windows.append(sorted((len(i.prompt), i.max_new) for i in win))
    assert windows[0] == windows[1]
    assert len(windows[0]) == math.ceil(spec["rate_per_s"] * seconds)
