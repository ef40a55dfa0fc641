"""Model step (device): model FLOPs of the tokens the steps processed in
the traced window, over the step program's device seconds times the
chips' peak.  Tokens and their positions come from ``run["work"]``
(``counts.span_flops`` spans); padding rows are not work."""
from bench import counts


def read(run):
    trace = run.get("trace")
    if not trace or not trace["step_device_s"]:
        return None
    flops = sum(counts.span_flops(run["dims"], s, e, head)
                for s, e, head in run["work"])
    if not flops:
        return None
    peak = run["peaks"]["bf16_flops_per_s"] * run["chips"]
    return 100.0 * flops / (trace["step_device_s"] * peak)
