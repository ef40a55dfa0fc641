"""Scheduler (serve/engine.py): the mean host time of one engine step
outside its wait for the step's results, over the window: the engine's
``step_host_ns`` counter over its ``iterations``."""


def read(run):
    start, end = run["counters"]["start"], run["counters"]["end"]
    if "step_host_ns" not in end:
        return None
    steps = end["iterations"] - start["iterations"]
    if not steps:
        return None
    return (end["step_host_ns"] - start["step_host_ns"]) / steps / 1e6
