"""Drive the qwen3-0.6b serving path once on a TPU, at its published widths.

    python chip_smoke.py              # one chip: phases a-e
    python chip_smoke.py --chips 4    # four chips: the multi-chip paths only
    python chip_smoke.py --tiny       # CPU rehearsal at smoke widths

The model is ``get_config("qwen3-0.6b")``, unreduced, with random weights
from ``--seed``.  Requests go through the entry points a user calls:
``repro.serve.api.LLM`` and the HTTP server of ``repro.serve.server``,
the code ``python -m repro.launch.serve --arch qwen3-0.6b --serve-http``
runs.  Every phase runs in this one process, which alone holds the chip.

  a  default engine: fused scheduler, paged KV, greedy comparator head;
     its paged attention is the compiled kernel on the chip (the
     dispatch's own rule), the jnp reference off it
  b  the same LLM behind ``make_server(port=0)``: streamed and plain
     ``POST /v1/completions`` and ``GET /v1/stats``; tokens equal a's
  c  the softmax baseline head; Theorem 1: tokens equal a's
  d  compiled Pallas kernels for the heads and verify bank too
     (``use_pallas``, chunk_size 64, spec_k 4):
     ``tpu_custom_call`` in the compiled step; tokens equal a's
  e  device-resident decode (host_stride 8); tokens equal a's

``--chips 4`` runs only what exists across chips and what it is compared
with: a tp=4 engine against a tp=1 one, and ``Router(replicas=4)``
against one LLM, with each replica's params and KV pools on its own
device.

Every token every phase emits is checked against a plain forward
(``lm.forward`` over the prompt and the tokens before it: no KV cache,
no paged pool, no serving head): it must be that forward's argmax, or
within ``NEAR_TIE`` logits of it.  Phase c against a (Theorem 1: the
same logits, another head) admits no divergence at all.  The other
pairs run different programs (other kernels, batch shapes, reduction
orders, a loop body) whose bf16 rounding differs, so a request may part
from a's at a near tie; the script prints where each request first
differs and the plain forward's top-2 logit margin there, and fails on
any token that is not within a near tie of the plain forward's argmax.
Times and memory printed along the way are smoke figures, not benchmark
numbers.  The last line of stdout is one JSON object naming the device;
it reads ``"ok": true`` only after every phase passed on a TPU at full
width.  With no TPU, and always under ``--tiny``, the script exits
non-zero.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import gc
import json
import sys
import threading
import time
import urllib.request
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# Logit gap below which a token counts as a bf16 near tie of the plain
# forward's argmax.  Programs that round differently (another batch
# shape, kernel, sharding or reduction order) moved the random-weight
# model's unit-scale logits by up to 0.0485 over 512 tokens on a TPU v5e
# (tp=4, tp=1 and the plain forward); 2^-3 leaves 2.5x room.  A fault
# moves the hidden state itself, and over the hundreds of tokens a phase
# emits it picks tokens far below the argmax.
NEAR_TIE = 2.0 ** -3


@dataclasses.dataclass(frozen=True)
class Sizes:
    slots: int
    max_len: int
    prompt_lens: tuple    # each prompt takes one of these lengths
    n_prompts: int
    max_new: int
    chunk_size: int
    spec_k: int
    host_stride: int
    n_http: int           # requests of phase b, every other one streamed


FULL = Sizes(slots=8, max_len=2048, prompt_lens=(64, 256, 1024),
             n_prompts=8, max_new=32, chunk_size=64, spec_k=4,
             host_stride=8, n_http=4)
TINY = Sizes(slots=4, max_len=128, prompt_lens=(8, 24, 40), n_prompts=6,
             max_new=8, chunk_size=16, spec_k=4, host_stride=4, n_http=4)


class Mismatch(Exception):
    """A run emitted a token its plain forward or its exact reference
    rules out."""


class CompileClock:
    """Seconds the XLA backend spent compiling while open (tracing and
    lowering are left in the wall time: nested jits would count twice)."""

    def __init__(self):
        self.seconds = 0.0

    def _on(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.seconds += duration

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


class Run:
    """Shared state of one smoke run: model, prompts, plain-forward
    verdicts."""

    def __init__(self, cfg, params, sizes: Sizes, seed: int):
        import jax
        import numpy as np

        from repro.models import lm

        self.cfg, self.params, self.sizes = cfg, params, sizes
        rng = np.random.default_rng(seed)
        self.prompts = [
            rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
            for n in rng.choice(sizes.prompt_lens, sizes.n_prompts)]

        def logits_before_generated(p, tokens, n):
            # rows n-1 .. L-2 of a forward over (1, L) tokens whose
            # first n are the prompt
            return lm.forward(p, cfg, {"tokens": tokens})[0][0, n - 1:-1]

        self._forward = jax.jit(logits_before_generated, static_argnums=2)
        self._plain: dict = {}

    def engine_kw(self, **kw):
        return dict(n_slots=self.sizes.slots, max_len=self.sizes.max_len,
                    **kw)

    def phase(self, name: str, what: str, fn):
        """Run ``fn() -> token lists`` and print its smoke figures."""
        import jax

        t0 = time.perf_counter()
        with CompileClock() as clock:
            tokens = fn()
        wall = time.perf_counter() - t0
        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        print(f"phase {name} ({what}): done  wall {wall:.1f} s  "
              f"compile {clock.seconds:.1f} s  "
              f"tokens {sum(len(t) for t in tokens)}  "
              f"peak_bytes_in_use {peak if peak is not None else 'n/a'}"
              "  [smoke figures, not benchmark numbers]", flush=True)
        return tokens

    def plain(self, r: int, gen):
        """The plain forward before each token of ``gen`` (request
        ``r``'s output): its top-2 ids ``(n, 2)``, their logits, and the
        logit of the token ``gen`` chose there ``(n,)``."""
        import numpy as np

        key = (r, tuple(gen))
        if key not in self._plain:
            prompt = self.prompts[r]
            seq = np.concatenate([prompt, np.asarray(gen, np.int32)])
            lg = np.asarray(self._forward(self.params, seq[None],
                                          len(prompt)), np.float32)
            top = np.argpartition(-lg, 1, axis=-1)[:, :2]
            vals = np.take_along_axis(lg, top, -1)
            order = np.argsort(-vals, axis=-1)
            self._plain[key] = (np.take_along_axis(top, order, -1),
                                np.take_along_axis(vals, order, -1),
                                lg[np.arange(len(gen)), list(gen)])
        return self._plain[key]

    def check_plain(self, what: str, outs) -> None:
        """Every token of ``outs`` must be the plain forward's argmax
        over its own prefix, or within ``NEAR_TIE`` logits of it."""
        n = ties = 0
        worst = 0.0
        for r, gen in enumerate(outs):
            if not gen:
                continue
            top, vals, chosen = self.plain(r, gen)
            gap = vals[:, 0] - chosen
            bad = [i for i, g in enumerate(gap) if g >= NEAR_TIE]
            if bad:
                i = bad[0]
                print(f"MISMATCH {what}: request {r} (prompt "
                      f"{len(self.prompts[r])} tokens) generated token {i} "
                      f"is {gen[i]}, {float(gap[i])!r} logits below the plain "
                      f"forward's argmax {top[i, 0]}", flush=True)
                raise Mismatch(f"{what}: request {r} token {i} is not the "
                               "plain forward's argmax")
            n += len(gen)
            ties += sum(1 for t, best in zip(gen, top[:, 0]) if t != best)
            worst = max(worst, float(gap.max()))
        print(f"{what} vs plain forward: {n - ties} of {n} tokens its "
              f"argmax, {ties} within a near tie of it (largest gap "
              f"{worst!r})", flush=True)

    def compare(self, what: str, want, got, *, exact: bool = False) -> None:
        """Print where ``got`` first parts from the reference ``want``
        in each request, with the plain forward's top-2 logit margin
        there; with ``exact`` any divergence fails the run.  Without it
        the tokens of both runs stand or fall by ``check_plain``."""
        if len(want) != len(got):
            raise Mismatch(f"{what}: {len(got)} outputs for "
                           f"{len(want)} prompts")
        same = 0
        for r, (a, b) in enumerate(zip(want, got)):
            a, b = list(a), list(b)
            if a == b:
                same += 1
                continue
            pos = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                       min(len(a), len(b)))
            top, vals, _ = self.plain(r, a if pos < len(a) else b)
            tag = "MISMATCH" if exact else "parted"
            print(f"{tag} {what}: request {r} (prompt "
                  f"{len(self.prompts[r])} tokens) first differs at "
                  f"generated token {pos}: reference "
                  f"{a[pos] if pos < len(a) else 'end'} vs "
                  f"{b[pos] if pos < len(b) else 'end'}; plain forward "
                  f"top-2 there ids {top[pos, 0]}, {top[pos, 1]}, logit "
                  f"margin {float(vals[pos, 0] - vals[pos, 1])!r}",
                  flush=True)
            if exact:
                raise Mismatch(f"{what}: request {r} differs at token "
                               f"{pos}")
        print(f"{what}: {same} of {len(want)} requests token-identical",
              flush=True)

    def check(self, what: str, ref, got, *, exact: bool = False) -> None:
        """A later phase's output: against phase a's, then on its own
        against the plain forward."""
        self.compare(f"{what} vs a", ref, got, exact=exact)
        self.check_plain(what, got)


def generate(llm, prompts, **params):
    from repro.serve.params import SamplingParams

    outs = llm.generate([p.copy() for p in prompts],
                        SamplingParams(**params))
    return [list(o.token_ids) for o in outs]


def _http(base: str, prompt, max_new: int, stream: bool):
    body = json.dumps({"prompt": [int(t) for t in prompt],
                       "max_new_tokens": max_new,
                       "stream": stream}).encode()
    req = urllib.request.Request(f"{base}/v1/completions", data=body,
                                 method="POST")
    with urllib.request.urlopen(req, timeout=900) as resp:
        if not stream:
            return json.loads(resp.read())["token_ids"]
        tokens = []
        for line in resp:
            line = line.strip()
            if line == b"data: [DONE]":
                return tokens
            if line.startswith(b"data: "):
                tokens.append(json.loads(line[6:])["token"])
    raise RuntimeError("event stream ended without [DONE]")


def serve_http(llm, prompts, max_new: int) -> list:
    """Phase b: the LLM behind the HTTP server, requests sent at once."""
    from repro.serve.server import make_server

    srv = make_server(llm, port=0)
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        with concurrent.futures.ThreadPoolExecutor(len(prompts)) as pool:
            futs = [pool.submit(_http, base, p, max_new, i % 2 == 1)
                    for i, p in enumerate(prompts)]
            tokens = [f.result() for f in futs]
        with urllib.request.urlopen(f"{base}/v1/stats", timeout=60) as r:
            stats = json.loads(r.read())
    finally:
        srv.shutdown()
        srv.server_close()
        thread.join(timeout=60)
        llm.stop_pump()          # re-raises an engine error of the pump
    if stats["engine"]["completed"] < len(prompts):
        raise RuntimeError(f"/v1/stats counts {stats['engine']['completed']}"
                           f" completed requests, expected >= "
                           f"{len(prompts)}")
    print(f"phase b: /v1/stats replicas[0].devices = "
          f"{stats['replicas'][0]['devices']}", flush=True)
    return tokens


def step_hlo(llm, width: int) -> str:
    """Compiled text of the engine's fused step (its own jitted step
    factory, as phase d dispatches it) at ``width`` query tokens over
    every slot, with the draft-verify group on."""
    import jax
    import jax.numpy as jnp

    from repro.serve import engine as engine_mod
    from repro.serve import sampler as sampler_mod

    eng = llm.engine
    b = eng.n_slots
    fn = engine_mod._jitted_step(
        eng.cfg, (sampler_mod.Greedy(),), eng.store.treedef,
        tuple(eng.store.paged_mask), eng.mesh, True)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    lowered = fn.lower(
        eng.params, i32(b, width), eng.store.pools,
        eng.store.dense_sub(range(b)), i32(b, eng.store.max_blocks_per_slot),
        i32(b, width), (i32(b),), i32(b), i32(b, width - 1))
    return lowered.compile().as_text()


def run_one_chip(run: Run) -> None:
    import jax

    from repro.serve.api import LLM

    s, cfg, params = run.sizes, run.cfg, run.params
    llm = LLM(params, cfg, **run.engine_kw())
    ref = run.phase("a", "default engine",
                    lambda: generate(llm, run.prompts,
                                     max_new_tokens=s.max_new))
    run.check_plain("phase a", ref)

    http = run.phase("b", "HTTP server over the same LLM",
                     lambda: serve_http(llm, run.prompts[:s.n_http],
                                        s.max_new))
    run.check("phase b (HTTP)", ref[:s.n_http], http)
    del llm
    gc.collect()

    llm = LLM(params, cfg, **run.engine_kw(head_mode="softmax"))
    got = run.phase("c", "softmax baseline head",
                    lambda: generate(llm, run.prompts,
                                     max_new_tokens=s.max_new))
    run.check("phase c (softmax head)", ref, got, exact=True)
    del llm
    gc.collect()

    cfg_p = dataclasses.replace(cfg, use_pallas=True)
    llm = LLM(params, cfg_p,
              **run.engine_kw(chunk_size=s.chunk_size))
    got = run.phase("d", f"Pallas kernels, chunk_size {s.chunk_size}, "
                         f"spec_k {s.spec_k}",
                    lambda: generate(llm, run.prompts,
                                     max_new_tokens=s.max_new,
                                     spec_k=s.spec_k))
    st = llm.stats
    print(f"phase d: prefill_chunks {st['prefill_chunks']}  drafted "
          f"{st['drafted']}  accepted {st['accepted']}", flush=True)
    width = 1 << s.spec_k.bit_length()       # the padded draft window
    txt = step_hlo(llm, width)
    if jax.default_backend() == "tpu":
        if "tpu_custom_call" not in txt:
            raise RuntimeError("phase d: no tpu_custom_call in the compiled"
                               " step; the Pallas kernels did not compile")
        n = txt.count('custom_call_target="tpu_custom_call"')
        print(f"phase d: compiled step (T={width}) holds {n} "
              "tpu_custom_call ops", flush=True)
    else:
        print("phase d: kernels run in interpret mode off the chip; no "
              "tpu_custom_call expected", flush=True)
    run.check("phase d (Pallas, chunked, spec)", ref, got)
    del llm
    gc.collect()

    llm = LLM(params, cfg, **run.engine_kw(host_stride=s.host_stride))
    got = run.phase("e", f"device-resident decode, host_stride "
                         f"{s.host_stride}",
                    lambda: generate(llm, run.prompts,
                                     max_new_tokens=s.max_new))
    run.check("phase e (host_stride)", ref, got)


def run_four_chips(run: Run) -> None:
    import jax

    from repro.serve.api import LLM
    from repro.serve.router import Router

    if len(jax.devices()) < 4:
        raise RuntimeError(f"--chips 4 needs 4 devices; JAX sees "
                           f"{len(jax.devices())}")
    s, cfg, params = run.sizes, run.cfg, run.params
    llm = LLM(params, cfg, **run.engine_kw())
    ref = run.phase("tp1", "one LLM, tp=1 (the reference)",
                    lambda: generate(llm, run.prompts,
                                     max_new_tokens=s.max_new))
    run.check_plain("tp=1", ref)
    del llm

    llm = LLM(params, cfg, **run.engine_kw(tp=4))
    got = run.phase("tp4", "one LLM, tp=4 over four chips",
                    lambda: generate(llm, run.prompts,
                                     max_new_tokens=s.max_new))
    run.compare("tp=4 vs tp=1", ref, got)
    run.check_plain("tp=4", got)
    del llm
    gc.collect()

    router = Router(params, cfg, replicas=4, **run.engine_kw())
    got = run.phase("router", "Router(replicas=4), tp=1 each",
                    lambda: generate(router, run.prompts,
                                     max_new_tokens=s.max_new))
    placed = []
    for r in router.replicas:
        eng = r.llm.engine
        devs = set(eng.devices)
        for pool in eng.store.pools:
            if pool is not None:
                devs |= set(pool.devices())
        if len(devs) != 1:
            raise RuntimeError(f"replica {r.idx} spans devices {devs}")
        placed.append(devs.pop())
    names = [rep["devices"] for rep in router.stats_payload()["replicas"]]
    print(f"router: replica devices {names}  routed "
          f"{[r.served for r in router.replicas]}", flush=True)
    if len(set(placed)) != 4:
        raise RuntimeError(f"router replicas share devices: {placed}")
    run.compare("Router(replicas=4) vs one LLM", ref, got)
    run.check_plain("Router(replicas=4)", got)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip paths (tp=4, a "
                         "4-replica router) and what they are compared "
                         "with")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse every phase at smoke widths (exits "
                         "non-zero: a rehearsal is not a chip smoke)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    import jax

    from repro.configs import get_config, smoke_config
    from repro.models import lm

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.tiny:
        print(f"chip_smoke: JAX found no TPU (devices[0] is "
              f"{dev.platform}); --tiny rehearses on the CPU",
              file=sys.stderr)
        return 2
    cfg = get_config("qwen3-0.6b")
    if args.tiny:
        cfg = smoke_config(cfg)
    params = lm.init_params(cfg, jax.random.PRNGKey(args.seed))
    run = Run(cfg, params, TINY if args.tiny else FULL, args.seed)
    print(f"chip_smoke: {cfg.name} d_model {cfg.d_model} layers "
          f"{cfg.n_layers} vocab {cfg.vocab_size} on {len(jax.devices())}"
          f" x {dev.device_kind}", flush=True)
    if args.chips == 4:
        run_four_chips(run)
    else:
        run_one_chip(run)
    if dev.platform != "tpu" or args.tiny:
        print("chip_smoke: every phase passed, but as a rehearsal, not at "
              "full width on a TPU", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(SRC))
    from repro.launch import compile_cache

    compile_cache.enable()
    sys.exit(main())
