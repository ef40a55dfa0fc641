"""The span and scope reduction against hand-computed values."""
import pytest

from bench import spans


def _devices():
    # one device, window [0, 200) ns; two runs of the step program
    # (jit_step at 10-60 and 110-150) and a small program at 80-90
    ops = [("while.5", 10, 50, None),              # container: left out
           ("fusion.1", 10, 20, "attention"),
           ("fusion.2", 30, 10, "mlp"),
           ("copy.3", 40, 20, None),               # unscoped leaf
           ("convert.4", 80, 10, None),            # not the step program
           ("fusion.1", 110, 30, "attention"),
           ("call.7", 140, 10, None),              # container
           ("fusion.8", 140, 10, "head")]
    modules = [("jit_step(3)", 10, 50), ("jit_small(1)", 80, 10),
               ("jit_step(3)", 110, 40)]
    return {"/device:TPU:0": {"ops": ops, "modules": modules}}


def _host():
    # step 1: dispatch 5-12 launches the run at 10-60, sync 12-65
    # step 2: dispatch 100-108 launches the run at 110-150, sync 108-155
    return [("serve.step", 0, 70), ("serve.dispatch", 5, 12),
            ("serve.sync", 12, 65), ("serve.emit", 65, 70),
            ("client.send", 72, 76),
            ("serve.step", 94, 160), ("serve.plan", 94, 100),
            ("serve.dispatch", 100, 108), ("host.gc", 102, 106),
            ("serve.sync", 108, 155), ("serve.emit", 155, 160)]


def test_scoped_leaf_seconds_leave_out_containers():
    red = spans.reduce(_devices(), _host(), (0, 200))
    assert red["step_program"] == "jit_step"
    assert red["scopes"] == {"attention": pytest.approx(50e-9),
                             "mlp": pytest.approx(10e-9),
                             "head": pytest.approx(10e-9)}
    # leaf ops inside step runs: 20 + 10 + 20 + 30 + 10 = 90 ns
    assert red["step_leaf_s"] == pytest.approx(90e-9)
    assert red["scoped_share"] == pytest.approx(70 / 90)
    assert red["unscoped_ops"] == [["copy.3", pytest.approx(20e-9)]]
    assert ["attention/fusion.1", pytest.approx(50e-9)] in red["device_ops"]
    assert ["while.5", pytest.approx(50e-9)] in red["device_ops"]


def test_gaps_take_the_innermost_host_span():
    red = spans.reduce(_devices(), _host(), (0, 200))
    # idle 60-80: serve.sync 60-65, serve.emit 65-70, no span 70-72,
    # client.send 72-76, no span 76-80; idle 90-110: no span 90-94,
    # serve.plan 94-100 (inside serve.step), serve.dispatch 100-102 and
    # 106-108 around host.gc 102-106, serve.sync 108-110
    idle = red["idle"]
    assert idle["serve.sync"] == pytest.approx(7e-9)
    assert idle["serve.emit"] == pytest.approx(5e-9)
    assert idle["client.send"] == pytest.approx(4e-9)
    assert idle["serve.plan"] == pytest.approx(6e-9)
    assert idle["serve.dispatch"] == pytest.approx(4e-9)
    assert idle["host.gc"] == pytest.approx(4e-9)
    assert "serve.step" not in idle
    assert idle["after jit_step"] == pytest.approx(6e-9)
    assert idle["after jit_small"] == pytest.approx(4e-9)
    assert red["idle_s"] == pytest.approx(40e-9)
    assert red["idle_named_share"] == pytest.approx(30 / 40)
    # each gap is labelled by the name that covers most of it; the
    # instants no span covers count for the program label
    assert red["idle_gaps"] == [["after jit_step", pytest.approx(20e-9)],
                                ["serve.plan", pytest.approx(20e-9)]]


def test_a_gap_no_span_covers_keeps_the_program_label():
    red = spans.reduce(_devices(), [], (0, 200))
    assert red["idle_gaps"] == [["after jit_step", pytest.approx(20e-9)],
                                ["after jit_small", pytest.approx(20e-9)]]
    assert red["idle_named_share"] == 0
    assert red["clock"] is None and red["clock_skew_ms"] is None
    assert red["engine_host_ms"] is None


def test_clock_check_and_engine_host_time():
    red = spans.reduce(_devices(), _host(), (0, 200))
    assert red["clock"] == 1.0 and red["step_runs"] == 2
    # runs 10-60 and 110-150 in dispatch-to-sync spans 5-65 and 100-155:
    # shifts from -5 to +5 ns keep both inside
    assert red["clock_skew_ms"] == pytest.approx([-5e-6, 5e-6])
    # step 1: 70 less 53 of sync; step 2: 66 less 47 of sync
    assert red["engine_host_ms"] == pytest.approx((17 + 19) / 2 / 1e6)
    # a device clock 8 ns early puts the first run before its dispatch
    early = _devices()
    lines = early["/device:TPU:0"]
    lines["modules"] = [(n, s - 8 if s < 100 else s, d)
                        for n, s, d in lines["modules"]]
    red = spans.reduce(early, _host(), (0, 200))
    assert red["clock"] == 0.5
    assert red["clock_skew_ms"] == pytest.approx([3e-6, 5e-6])


@pytest.mark.parametrize("path,scope", [
    ("jit(step)/while/body/attention/dot_general", "attention"),
    ("jit(step)/verify/jit(cumprod)/add", "verify"),
    ("mlp/dot_general", "mlp"),
    ("jit(step)/while/body/closed_call/attention/kv_write/scatter",
     "kv_write"),
    ("jit(step)/transpose", None),
    ("", None)])
def test_scope_of_an_op_path(path, scope):
    assert spans.scope_of(path) == scope


def test_capture_keeps_the_trace_and_reads_the_program_spans(tmp_path):
    """A traced window at the tiny size on the CPU (no device planes, so
    only the host side reads): the engine's spans are in the trace."""
    from bench.tests import tiny

    res = spans.capture("qwen3-0.6b.chat", 2 ** 40 + 5, 2.0, True,
                        str(tmp_path), require_tpu=False,
                        config_override=tiny.CONFIG,
                        traffic_override=tiny.TRAFFIC["chat"])
    assert res["steps"] > 0 and res["itl_samples"] > 0
    assert 0 < res["step_fill_share"] <= 100
    assert res["engine_host_ms"] > 0
    xplane, = tmp_path.glob("*.xplane.pb")
    _, host = spans.load(str(xplane))
    names = {name for name, _, _ in host}
    assert {"serve.step", "serve.dispatch", "serve.sync", "serve.emit",
            "serve.submit", "client.send", "client.sample_kv"} <= names
