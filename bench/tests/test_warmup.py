"""The warm-up before the pre-roll: what it plans, and that it leaves the
engine as it found it."""
import json

import jax

from bench import harness, traffic, warmup
from bench.tests import tiny


def test_plan_covers_the_chat_window_in_powers_of_two():
    spec = traffic.load("chat")["warm"]
    engine = json.loads((harness.ROOT / "bench/configs/qwen3-0.6b.json")
                        .read_text())["engine"]
    p = warmup.plan(spec, engine)
    assert p["batches"] == [64]                     # 33-64 rows in flight
    assert p["tables"] == [64, 128, 256]            # 33-192 blocks
    assert p["widths"] == [1, 2, 4, 8, 16, 32, 64]  # up to chunk_size
    assert p["heads"] == [0.125, 0.25, 0.5]


def test_buckets_are_the_powers_of_two_between():
    assert warmup.buckets(17, 64) == [32, 64]
    assert warmup.buckets(1, 5) == [1, 2, 4, 8]
    assert warmup.buckets(64, 64) == [64]


def _tiny_llm():
    cell = harness.resolve(harness.load_benchmark(), "qwen3-0.6b.chat")
    cell.config = {**cell.config, **tiny.CONFIG}
    return harness.build(cell, 2 ** 40 + 9, require_tpu=False)


def test_warm_leaves_no_request_and_an_empty_pool():
    sv = _tiny_llm()
    spec = {"rows": [3, 4], "context": [100, 200], "heads": [0.5]}
    steps = warmup.warm(sv.llm, spec, sv.cell.config["engine"],
                        sv.dims["vocab"])
    assert steps > 0
    assert not sv.llm.engine.has_work
    assert sv.llm.kv_usage()["blocks_in_use"] == 0   # nothing cached


def test_a_second_warm_up_compiles_nothing():
    """The warm-up is the same work every time: what it compiled once
    it finds again."""
    sv = _tiny_llm()
    spec = {"rows": [3, 4], "context": [100, 200], "heads": [0.5]}
    engine = sv.cell.config["engine"]
    warmup.warm(sv.llm, spec, engine, sv.dims["vocab"])
    seen = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: seen.append(event)
        if event == harness.COMPILE_EVENT else None)
    warmup.warm(sv.llm, spec, engine, sv.dims["vocab"])
    assert seen == []
