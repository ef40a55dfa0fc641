"""Serving driver: run the continuous-batching engine from the CLI.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --requests 8 --slots 4 \
      [--head-mode reduced|softmax|fused|sharded|temperature] \
      [--kv-layout paged|dense] [--top-k 4 --temperature 0.8] \
      [--spec-k 4] [--chunk-size 16 [--token-budget 64]] \
      [--host-stride 8] [--serve-http 8000]

``--serve-http PORT`` swaps the batch run for the network frontend
(serve/server.py): an SSE ``POST /v1/completions`` + ``GET /v1/stats``
HTTP server over the ``LLM`` facade, engine pumped from a background
thread — per-request SamplingParams arrive in the request body.

The head spec resolves to a ``Sampler`` (serve/sampler.py) — the engine,
the model API and this driver all consume the object; no head_mode
string ever reaches the model.  ``--head-mode sharded`` builds a
(1, n_devices) host mesh and runs every decode step's head through
``sharded_reduced_head``: the lm_head weight is vocab-sharded over
'model', each shard runs the fused comparator on its vocab slice, and
only one (val, idx) pair per row per shard crosses the wire — the
multi-chip form of the paper's reduced unit.  Run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to exercise it on
a CPU host.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, smoke_config
from repro.launch import compile_cache, mesh as mesh_mod
from repro.models import lm
from repro.serve import sampler as sampler_mod
from repro.serve.engine import Request, ServeEngine


def _run_batch_router(args, cfg, params, engine_kw):
    """The batch run over a multi-replica Router: same trace as the
    single-engine path, routed by prefix affinity / least-load, served
    to completion with inline round-robin stepping, aggregate stats
    printed with the per-replica request split."""
    from repro.serve.params import SamplingParams
    from repro.serve.router import Router

    router = Router(params, cfg, replicas=args.replicas, tp=args.tp,
                    **engine_kw)
    rng = np.random.default_rng(args.seed)
    prompts, plist = [], []
    for rid in range(args.requests):
        plen = int(rng.integers(4, 24))
        prompts.append(rng.integers(0, cfg.vocab_size,
                                    plen).astype(np.int32))
        # explicit per-request seed: the facade's (engine seed, rid)
        # default depends on which replica got the request, so sampled
        # streams would vary with routing — pinning the seed makes the
        # trace reproducible whatever the replica split.
        plist.append(SamplingParams(max_new_tokens=args.max_new,
                                    spec_k=args.spec_k, top_k=args.top_k,
                                    temperature=args.temperature,
                                    head_mode=args.head_mode,
                                    seed=args.seed * 100003 + rid))
    t0 = time.perf_counter()
    outs = router.generate(prompts, plist)
    dt = time.perf_counter() - t0
    stats = router.stats
    split = "/".join(str(r.served) for r in router.replicas)
    toks = sum(len(o.token_ids) for o in outs)
    print(f"replicas={args.replicas} tp={args.tp or 1} "
          f"routed={split} served={stats['completed']} "
          f"tokens={toks} decode_steps={stats['decode_steps']} "
          f"preempt={stats['preemptions']} wall={dt:.2f}s")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--head-mode", default="reduced",
                    choices=["reduced", "softmax", "fused", "sharded",
                             "temperature"])
    ap.add_argument("--kv-layout", default="paged",
                    choices=["paged", "dense"])
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="KV pool size in blocks (default: n_slots * "
                         "ceil(max_len/block_size); smaller overcommits)")
    ap.add_argument("--top-k", type=int, default=1,
                    help=">1: top-k sampling via the k-winner comparator")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--spec-k", type=int, default=0,
                    help=">0: speculative decoding — up to K prompt-"
                         "lookup draft tokens per step, verified by the "
                         "reduced comparator in one forward (greedy "
                         "only; bit-identical output, 1..K+1 tokens per "
                         "iteration)")
    ap.add_argument("--scheduler", default="fused",
                    choices=["fused", "cohort"],
                    help="fused: ONE jitted ragged decode step per "
                         "iteration over all slots (default); cohort: "
                         "the PR 2 position-cohort baseline")
    ap.add_argument("--chunk-size", type=int, default=None,
                    help="chunked prefill: admit prompts into the fused "
                         "step this many tokens per iteration instead of "
                         "one monolithic prefill call — bounds the stall "
                         "a long prompt inflicts on in-flight decodes "
                         "and admits with only the first chunk's KV "
                         "cover free (fused scheduler + paged KV only; "
                         "output is bit-identical either way)")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="cap on real tokens (decode rows + prefill "
                         "chunk widths) per fused iteration; chunk "
                         "widths shrink to fit, decode rows are always "
                         "served (requires --chunk-size)")
    ap.add_argument("--host-stride", type=int, default=None,
                    help=">=1: device-resident decode — run up to K "
                         "fused iterations per host dispatch inside one "
                         "jitted lax.while_loop (sampling on device with "
                         "per-request PRNG keys; outputs identical "
                         "across strides); mutually exclusive with "
                         "--spec-k")
    ap.add_argument("--prefix-cache", dest="prefix_cache",
                    action="store_true", default=True,
                    help="prefix sharing (default on): completed "
                         "requests publish their full-block KV runs "
                         "into a trie; later requests with the same "
                         "prompt prefix attend through the SAME pool "
                         "blocks (copy-on-write) and prefill only their "
                         "suffix — needs --chunk-size; outputs are "
                         "token-identical either way")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false",
                    help="disable prefix sharing (every request "
                         "prefills and stores its own KV)")
    from repro.core.attn_approx import VARIANTS

    ap.add_argument("--attn-approx", default=None, choices=list(VARIANTS),
                    help="approximate-attention score function for the "
                         "paged decode path: base2 (shift+LUT 2^x), "
                         "pseudo (2^x / sum 2^x), pwl (piecewise-linear "
                         "exp), maxonly (winner-take-all comparator — "
                         "the paper's unit as an attention datapath); "
                         "default exact")
    ap.add_argument("--attn-window", type=int, default=None,
                    help="sliding-window MASK over the paged kv view "
                         "(decode attends to the last N positions only; "
                         "KV is still fully stored, so speculation / "
                         "prefix sharing compose) — with "
                         "--attn-approx maxonly this is the paper's "
                         "comparator over a sliding bus")
    ap.add_argument("--tp", type=int, default=None,
                    help=">1: tensor-parallel trunk over a (1, N) "
                         "'model' mesh — Megatron column/row weight "
                         "layout, head-wise sharded KV pools, and the "
                         "comparator head upgraded to its vocab-sharded "
                         "form (only (val, idx) pairs cross shards at "
                         "the head, never a logit row); outputs are "
                         "bit-identical to --tp 1.  On a CPU host set "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N first")
    ap.add_argument("--replicas", type=int, default=1,
                    help=">1: serve through a multi-replica Router — N "
                         "independent engines behind one admission "
                         "queue with session/prefix affinity routing "
                         "and aggregated stats (serve/router.py); "
                         "composes with --tp (each replica gets its own "
                         "device slice when replicas*tp devices exist)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--serve-http", type=int, default=None, metavar="PORT",
                    help="instead of the batch run: start the SSE HTTP "
                         "frontend (POST /v1/completions, GET /v1/stats) "
                         "on this port and serve until interrupted")
    ap.add_argument("--http-host", default="127.0.0.1")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    params = lm.init_params(cfg, jax.random.PRNGKey(args.seed))
    if args.tp is not None and args.tp > 1 \
            and args.head_mode in ("reduced", "fused"):
        # mirror the engine's tp upgrade HERE so the pre-resolved
        # sampler the batch path submits (and the head_mode the spec
        # path passes through SamplingParams) is the vocab-sharded
        # comparator too, not just the engine default.
        args.head_mode = "sharded"
    sampler = sampler_mod.resolve(args.head_mode, args.top_k,
                                  args.temperature, cfg=cfg)
    mesh = None
    if args.tp is not None:
        # --tp builds (and validates) its own (1, N) mesh inside the
        # engine (ServeEngine tp=); the legacy sharded-head mesh below
        # would fight it over the 'model' axis size.
        pass
    elif sampler.needs_mesh:
        # vocab-sharded head: all devices on 'model'; the fused step's
        # batch size tracks the active-slot count, so the batch stays
        # replicated.
        mesh = mesh_mod.make_host_mesh(model=len(jax.devices()))
    if args.replicas < 1:
        raise SystemExit(f"--replicas {args.replicas}: must be >= 1")
    engine_kw = dict(n_slots=args.slots, max_len=args.max_len,
                     eos_id=1, head_mode=args.head_mode,
                     kv_layout=args.kv_layout, block_size=args.block_size,
                     num_blocks=args.num_blocks, scheduler=args.scheduler,
                     chunk_size=args.chunk_size,
                     token_budget=args.token_budget,
                     host_stride=args.host_stride,
                     prefix_cache=args.prefix_cache,
                     attn_approx=args.attn_approx,
                     attn_window=args.attn_window,
                     seed=args.seed)
    if args.serve_http is not None:
        from repro.serve.server import serve_forever

        if args.replicas > 1:
            from repro.serve.router import Router

            llm = Router(params, cfg, replicas=args.replicas, tp=args.tp,
                         **engine_kw)
        else:
            from repro.serve.api import LLM

            llm = LLM(params, cfg, tp=args.tp, mesh=mesh, **engine_kw)
        serve_forever(llm, host=args.http_host, port=args.serve_http)
        return
    if args.replicas > 1:
        _run_batch_router(args, cfg, params, engine_kw)
        return
    eng = ServeEngine(params, cfg, tp=args.tp, mesh=mesh, **engine_kw)
    rng = np.random.default_rng(args.seed)
    for rid in range(args.requests):
        plen = int(rng.integers(4, 24))
        prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
        if args.spec_k:
            from repro.serve.params import SamplingParams

            # pass every sampling knob through so invalid combinations
            # (spec_k with --top-k > 1, a non-comparator --head-mode)
            # fail loudly in SamplingParams/submit instead of silently
            # serving greedy
            eng.submit(Request(rid, prompt, params=SamplingParams(
                max_new_tokens=args.max_new, spec_k=args.spec_k,
                top_k=args.top_k, temperature=args.temperature,
                head_mode=args.head_mode)))
        else:
            eng.submit(Request(rid, prompt, max_new_tokens=args.max_new,
                               sampler=sampler))
    t0 = time.perf_counter()
    stats = eng.run()
    dt = time.perf_counter() - t0
    spec = (f"drafted={stats['drafted']} accepted={stats['accepted']} "
            f"acceptance={stats['acceptance_rate']:.2f} "
            if args.spec_k else "")
    chunk = (f"prefill_chunks={stats['prefill_chunks']} "
             if eng.chunk_size is not None else "")
    snap = eng.snapshot()
    stride = (f"host_syncs={stats['host_syncs']} "
              f"tok/dispatch={snap['tokens_per_dispatch']:.2f} "
              if eng.host_stride is not None else "")
    prefix = (f"prefix_hits={stats['prefix_hits']} "
              f"prefix_hit_tokens={stats['prefix_hit_tokens']} "
              f"cow_copies={snap['cow_copies']} "
              if eng.prefix_cache else "")
    print(f"sampler={sampler} kv={args.kv_layout} sched={args.scheduler} "
          f"{chunk}{stride}{prefix}"
          f"served={stats['completed']} decode_steps={stats['decode_steps']} "
          f"iterations={stats['iterations']} "
          f"rows/step={stats['fused_rows'] / max(stats['decode_steps'], 1):.2f} "
          f"preempt={stats['preemptions']} {spec}wall={dt:.2f}s")


if __name__ == "__main__":
    main()
