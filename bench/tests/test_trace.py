"""The trace reduction against hand-computed values."""
import pytest

from bench import trace


def _devices():
    # one device, window [0, 100) ns: two step runs and a small program
    ops = [("fusion.1", 10, 20), ("dot.2", 25, 10),     # overlap: busy 10-35
           ("fusion.1", 50, 10),                        # busy 50-60
           ("copy.3", 90, 20)]                          # clipped to 90-100
    modules = [("jit_step(7)", 10, 25), ("jit_small(2)", 50, 10),
               ("jit_step(7)", 90, 20)]
    return {"/device:TPU:0": {"ops": ops, "modules": modules}}


def test_busy_union_and_window():
    red = trace.reduce(_devices(), (0, 100))
    assert red["busy_s"] == pytest.approx((25 + 10 + 10) / 1e9)
    assert red["window_s"] == pytest.approx(100 / 1e9)


def test_step_program_is_the_module_with_most_time():
    red = trace.reduce(_devices(), (0, 100))
    assert red["step_program"] == "jit_step"
    assert red["step_count"] == 2
    assert red["step_device_s"] == pytest.approx(45 / 1e9)
    assert red["step_ms"] == pytest.approx(1e3 * 45 / 1e9 / 2)


def test_breakdown_ordering():
    red = trace.reduce(_devices(), (0, 100))
    assert [n for n, _ in red["device_ops"]] == ["fusion.1", "copy.3",
                                                 "dot.2"]
    assert red["device_ops"][0][1] == pytest.approx(30 / 1e9)
    # gaps 35-50 (15 ns, after jit_step) and 60-90 (30 ns, after jit_small)
    assert red["idle_gaps"] == [["after jit_small", pytest.approx(30 / 1e9)],
                                ["after jit_step", pytest.approx(15 / 1e9)]]


def test_devices_are_averaged():
    d = _devices()
    d["/device:TPU:1"] = {"ops": [("fusion.1", 0, 100)],
                          "modules": [("jit_step(7)", 0, 100)]}
    red = trace.reduce(d, (0, 100))
    assert red["busy_s"] == pytest.approx((45 + 100) / 2 / 1e9)
    assert red["step_count"] == 1.5


def test_no_events_reads_nothing():
    red = trace.reduce({}, (0, 100))
    assert red["busy_s"] == 0 and red["step_ms"] is None
