"""Find a cell's knee: one set-up, then one window at each offered rate.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 0.5,1,2

Prints one JSON line per rate: the latency tails, tokens per second
delivered and offered (the output tokens asked by the requests due in
the window, over its seconds), the 95th percentile of the wait from send
to admission, how many requests due in the window were still without a
first token when it closed (a backlog that grows with the rate marks the
knee), and how late the sender ran.  Used once, when a cell is defined; the benchmark's own
runs never sweep.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

RATE_KEY = {"open": "rate_per_s", "sessions": "session_rate_per_s",
            "closed": "clients"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    from bench import harness, traffic

    cell = harness.resolve(harness.load_benchmark(), args.workload)
    sv = harness.build(cell, args.seed)
    key = RATE_KEY[cell.traffic["kind"]]
    for rate in [float(r) for r in args.rates.split(",")]:
        spec = dict(cell.traffic, **{key: rate if key != "clients"
                                     else int(rate)})
        if "preroll_rate_per_s" in spec:      # the pre-roll scales along
            spec["preroll_rate_per_s"] = rate * (
                cell.traffic["preroll_rate_per_s"] / cell.traffic[key])
        sv.cell.traffic = spec
        sched = traffic.generate(spec, args.seed, args.seconds,
                                 sv.dims["vocab"])
        t = time.perf_counter()
        m = harness.measure(sv, sched, args.seconds)
        client = m["client"]
        values, seen = harness.latencies(client, m["w0"], m["w1"],
                                         m["deadline"])
        waiting = sum(1 for r in seen["due"] if r.refused is None
                      and not [s for s in client.stamps.get(r.rid, [])
                               if s[0] < m["w1"]])
        wait = [1e3 * (r.req.t_admit - r.sent) for r in seen["due"]
                if r.req is not None and r.req.t_admit is not None]
        offered = sum(r.max_new for r in seen["due"]) / args.seconds
        row = {"rate": rate, **values, **{k: v for k, v in seen.items()
                                          if k != "due"},
               "offered_tokens_per_s": offered,
               "queue_wait_p95_ms": harness.percentile(wait, 95),
               "waiting_at_close": waiting,
               "queued_after_drain": m["queued_after_drain"],
               "kv_peak_blocks": max(client.kv_samples, default=None),
               "point_s": time.perf_counter() - t}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
