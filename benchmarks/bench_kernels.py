"""Kernel-level benchmark: the fused reduced head vs the unfused pipeline.

On this CPU container the Pallas kernel runs in interpret mode (not
representative), so the TPU claim is made through bytes accounting:

  unfused: matmul writes (B,V) logits to HBM, softmax reads+writes (B,V),
           argmax reads (B,V)            -> >= 3*B*V*4 bytes beyond inputs
  fused:   logits stay in VMEM; HBM traffic is h + W + (B) outputs only

We report (a) the analytic HBM-byte model, (b) XLA-compiled flops/bytes of
both pipelines, (c) wall-clock of the XLA paths on this host, and
(d) correctness of the Pallas kernel vs its oracle at bench shapes.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref

SHAPES = [(128, 5120, 151936),   # qwen3-32b decode batch
          (128, 1024, 151936),   # qwen3-0.6b
          (32, 1024, 256206)]    # seamless head
BENCH = [(64, 512, 32064)]       # small enough to run on CPU


def analytic_bytes(B, D, V, dtype_bytes=2):
    inputs = B * D * dtype_bytes + D * V * dtype_bytes
    unfused = inputs + 4 * B * V * 4 + B * 4   # logits w + softmax r/w + argmax r
    fused = inputs + B * 8                     # (idx, val) only
    return unfused, fused


def run(verbose=True):
    rows = []
    for B, D, V in SHAPES:
        un, fu = analytic_bytes(B, D, V)
        rows.append(dict(B=B, D=D, V=V, unfused=un, fused=fu))
        if verbose:
            print(f"({B},{D},{V}): head HBM bytes unfused={un/1e9:.2f}GB "
                  f"fused={fu/1e9:.2f}GB saving={un/fu:.2f}x")
    for B, D, V in BENCH:
        h = jax.random.normal(jax.random.PRNGKey(0), (B, D))
        w = jax.random.normal(jax.random.PRNGKey(1), (D, V))

        def unfused(hh, ww):
            logits = hh @ ww
            probs = jax.nn.softmax(logits, -1)
            return jnp.argmax(probs, -1)

        f_un = jax.jit(unfused)
        f_fu = jax.jit(lambda hh, ww: ref.fused_argmax_head(hh, ww))
        for name, f in [("unfused", f_un), ("fused_xla", f_fu)]:
            f(h, w).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(10):
                out = f(h, w)
            out.block_until_ready()
            us = (time.perf_counter() - t0) / 10 * 1e6
            ca = f.lower(h, w).compile().cost_analysis()
            rows.append(dict(B=B, D=D, V=V, name=name, us=us,
                             flops=ca.get("flops"),
                             bytes=ca.get("bytes accessed")))
            if verbose:
                print(f"({B},{D},{V}) {name:10s} {us:9.1f}us "
                      f"bytes={ca.get('bytes accessed', 0):.2e}")
        # pallas kernel correctness at bench shape
        got = ops.fused_argmax_head(h, w, use_pallas=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(f_fu(h, w)))
        if verbose:
            print(f"({B},{D},{V}) pallas(interpret) == oracle: True")
    return rows


def main():
    rows = run()
    for r in rows:
        if "name" in r:
            print(f"kernel_{r['name']}_{r['B']}x{r['D']}x{r['V']},"
                  f"{r['us']:.1f},bytes={r['bytes']:.3e}")
        else:
            print(f"kernel_hbm_model_{r['B']}x{r['D']}x{r['V']},0,"
                  f"saving={r['unfused']/r['fused']:.2f}x")


if __name__ == "__main__":
    main()
