"""Head-unit cost across class counts k (the paper's '1000-class' claim).

Three cost views per unit, for k from 10 to the largest assigned vocab
(256206, seamless-m4t):
  1. arithmetic-op inventory (the paper's circuit-size argument);
  2. compiled HLO flops/bytes of each unit's predict fn (XLA, CPU);
  3. measured wall-clock of the jitted predict fn on this host.

The reduced unit needs zero exp/div/LUT at every k and wins all three.
"""
import time

import jax
import jax.numpy as jnp

from repro.core import PREDICT_FNS, reduced_softmax_predict, unit_op_counts

KS = [10, 100, 1000, 32064, 151936, 256206]
BATCH = 64


def _timed(fn, x, iters=20):
    fn(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(x)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6  # us


def run(verbose=True):
    units = dict(PREDICT_FNS)
    units["reduced (ours)"] = lambda x: reduced_softmax_predict(x)
    rows = []
    for k in KS:
        x = jax.random.normal(jax.random.PRNGKey(k), (BATCH, k))
        for name, fn in units.items():
            jfn = jax.jit(fn)
            lowered = jfn.lower(x)
            ca = lowered.compile().cost_analysis()
            us = _timed(jfn, x)
            rows.append(dict(k=k, unit=name, us=us,
                             flops=ca.get("flops", 0.0),
                             bytes=ca.get("bytes accessed", 0.0)))
        if verbose:
            base = next(r for r in rows if r["k"] == k and
                        r["unit"] == "softmax")
            red = next(r for r in rows if r["k"] == k and
                       r["unit"] == "reduced (ours)")
            print(f"k={k:7d}  softmax {base['us']:9.1f}us "
                  f"{base['flops']:.2e}fl | reduced {red['us']:9.1f}us "
                  f"{red['flops']:.2e}fl | speedup {base['us']/red['us']:5.2f}x"
                  f" flop-saving {base['flops']/max(red['flops'],1):7.1f}x")
    return rows


def main():
    rows = run()
    for k in KS:
        base = next(r for r in rows if r["k"] == k and r["unit"] == "softmax")
        red = next(r for r in rows if r["k"] == k and
                   r["unit"] == "reduced (ours)")
        print(f"head_unit_k{k},{red['us']:.1f},speedup_vs_softmax="
              f"{base['us']/red['us']:.2f}")
    ops = unit_op_counts(1000)
    print(f"head_unit_ops_k1000,0,softmax_exp={ops['softmax']['exp']}"
          f"_reduced_exp={ops['reduced (ours)']['exp']}")


if __name__ == "__main__":
    main()
