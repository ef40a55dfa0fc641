"""The per-layer readers of the engine's counters: their value over the
window, and nothing (not an error) from a program without the counter."""
import pytest

from bench import harness

START = {"iterations": 10, "step_tokens": 100, "step_slots": 1000,
         "step_host_ns": 5_000_000}
END = {"iterations": 30, "step_tokens": 340, "step_slots": 5000,
       "step_host_ns": 205_000_000}


@pytest.mark.parametrize("metric,value", [("step_fill_share", 6.0),
                                          ("engine_host_ms", 10.0)])
def test_counter_readers(metric, value):
    read = harness.reader(metric)
    run = {"counters": {"start": START, "end": END}}
    assert read(run) == pytest.approx(value)
    # a program without the counter (the parent) reads nothing
    old = {"iterations": 10, "prefill_tokens": 5}
    assert read({"counters": {"start": old, "end": old}}) is None
    # an empty window reads nothing
    assert read({"counters": {"start": START, "end": START}}) is None
