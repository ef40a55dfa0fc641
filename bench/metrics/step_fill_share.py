"""Scheduler (serve/engine.py): the real query tokens the step programs
were given over the query slots they ran (padded rows x query width),
over the window: the engine's ``step_tokens`` and ``step_slots``
counters."""


def read(run):
    start, end = run["counters"]["start"], run["counters"]["end"]
    if "step_slots" not in end:
        return None
    slots = end["step_slots"] - start["step_slots"]
    if not slots:
        return None
    return 100.0 * (end["step_tokens"] - start["step_tokens"]) / slots
