"""Model step (device): mean device time of one execution of the step
program, from the trace."""


def read(run):
    trace = run.get("trace")
    return trace["step_ms"] if trace else None
