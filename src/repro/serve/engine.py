"""Serving engine: continuous batching over a paged KV cache, with the
reduced softmax unit as the decode head.

The inference-accelerator story of the paper, at engine level:

  - fixed B decode slots over a SHARED, BLOCK-PAGED KV pool (block table
    per slot, free-list allocator — see serve/paged_kv.py); slots free
    their blocks on EOS/max_tokens and are refilled from the queue;
  - decode is RAGGED and FUSED: every engine iteration runs exactly ONE
    jitted decode step over ALL active slots, regardless of where each
    sequence is — ``positions`` is a per-row vector all the way down
    (model, masks, RoPE, the paged-attention kernel's scalar-prefetch
    operand).  The old scheduler sharded actives into position cohorts
    (four slots at four positions = four batch≈1 jitted calls per
    iteration), throwing away exactly the batching headroom the reduced
    head buys; now ``stats['decode_steps'] == stats['iterations']``;
  - mixed sampling never fragments the step: the fused call runs the
    trunk ONCE over all rows, then applies each distinct
    ``sampler.device_form()`` head to its own row subset inside the same
    jitted body (row indices are traced operands; the canonical group
    tuple is the jit key) — Greedy, TopK and Temperature traffic share
    one compiled step;
  - admission is PAGED-NATIVE: the jitted prefill scatters the prompt's
    K/V straight into the slot's freshly-allocated pool blocks
    (``api.serve_prefill_paged``); the dense prefill cache never
    round-trips through the host.  A scheduler interleaves prefill and
    decode: each iteration admits up to ``prefill_per_step`` queued
    requests into free slots (subject to block availability; an
    exhausted pool defers admission or preempts the youngest slot back
    to the queue);
  - prefill is CHUNKED on request (``chunk_size=C``): admission stops
    being a separate jitted call — a pending prompt is scattered into
    its pool blocks ``C`` tokens at a time as PREFILL-CHUNK ROWS inside
    the same fused ragged step that serves the decode rows, riding the
    (B, T) per-(row, query) position plumbing speculation added.  A
    chunk's logits are never materialized (the trunk just writes K/V;
    no head reads it) except for the FINAL chunk, whose last position
    feeds the row's sampler head and emits the request's first token —
    so one long prompt no longer head-of-line-blocks every decoding
    slot behind a monolithic prefill call, and the engine has exactly
    ONE jitted callable per iteration regardless of admission state.
    ``token_budget`` caps the real tokens (decode + draft + chunk) a
    single iteration may carry: chunk widths shrink to fit, every
    prefilling slot keeps >= 1 token of progress, and blocks allocate
    incrementally per chunk (``store.ensure_capacity``) instead of
    whole-prompt upfront.  Admissions are packed by LENGTH BUCKET
    (pow-2 first-chunk width, bounded lookahead past the queue head —
    the tensor2tensor bucketing-by-length idiom) so a mixed-length
    admission burst does not widen the step for everyone; the queue
    HEAD is always offered first, so FIFO admission stays
    starvation-free.  Chunked == one-shot token-exactly: a chunk row
    recomputes the same K/V into the same pool cells and the final
    chunk's hidden state equals the one-shot prefill's last position
    (asserted by tests/test_serve_chunked.py);
  - sampling is a ``Sampler`` object (serve/sampler.py): ``Greedy`` IS
    the reduced softmax unit (fused comparator — argmax over ``h @ W``
    with the (B, V) logits never materialized; no exp, no normalizing
    sum, no divide — Theorem 1), ``TopK`` the k-winner comparator with
    an O(k) host softmax, ``Temperature`` Gumbel-max over the logit row,
    ``SoftmaxBaseline`` the full unit for A/B runs;
  - decode is SPECULATIVE on request (``SamplingParams(spec_k=K)``):
    the engine's Drafter (serve/spec.py; default model-free
    prompt-lookup) proposes up to K draft tokens per slot, the fused
    step runs the trunk over each row's (last token + drafts) window at
    per-(row, query) positions, and the COMPARATOR verifies all K
    positions at once (accept draft t_i iff argmax(logits_i) == t_i —
    Theorem 1, repeated; ``kernels.ops.verify_draft``), emitting
    1..K+1 tokens per iteration, bit-identical to non-speculative
    greedy.  Rejected drafts rewind O(1): the slot position simply
    doesn't advance over them (the kv_pos <= positions masks make the
    stale pool rows invisible) and whole surplus blocks return to the
    free list (``store.rewind``).  Non-speculating rows ride along at
    width 1 in the same jitted call.
  - decode is DEVICE-RESIDENT on request (``host_stride=K``): instead
    of one host round-trip per token, each iteration dispatches ONE
    jitted ``lax.while_loop`` (``api.serve_decode_multi``) that runs up
    to K fused decode iterations entirely on device — trunk forward,
    K/V scatter, sampler head and the feed-back of the sampled token —
    and returns a (B, K) token block plus per-row emit counts.  Every
    per-row stop condition the DEVICE can know (remaining
    ``max_new_tokens``, the ``max_len`` ceiling, block-table capacity)
    is folded into a per-row emit cap before dispatch; the eos id
    halts a row inside the loop.  The host then DRAINS the block
    through the ordinary per-token emission path, so stop SEQUENCES
    become a bounded-lag host check: at most K-1 extra tokens are
    generated past a match, trimmed before emission, their KV rewound
    O(1) (``store.rewind``).  Sampling inside the loop is KEYED: each
    request carries a JAX PRNG key split exactly once per emitted
    token (``Sampler.sample_device`` / host mirror ``pick_keyed``), so
    generations are bit-identical across every ``host_stride`` —
    admission, preemption and chunked prefill synchronize at stride
    boundaries (iterations with a mid-prefill slot fall back to the
    legacy single fused step, still keyed).  ``spec_k`` is mutually
    exclusive with ``host_stride`` (both amortize the same host
    round-trip; composing them is future work), and stats grow
    ``host_syncs`` (jitted dispatches) and ``emitted_tokens`` —
    ``tokens_per_dispatch`` in ``snapshot()`` is the amortization
    actually achieved.

``scheduler='cohort'`` keeps the PR 2 position-cohort scheduling (one
fused call per (position, head) group) as the measurable baseline the
ragged fused step is benchmarked against; ``kv_layout='dense'`` keeps
the seed engine's per-slot ``max_len`` cache as the byte-identity oracle
the paged path is tested against.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from collections import deque
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.kernels import paged_attention as paged_kernel
from repro.models import api, lm
from repro.parallel import env
from repro.serve import sampler as sampler_mod
from repro.serve.outputs import TokenChunk
from repro.serve.paged_kv import PagedKVStore, pow2 as _pow2
from repro.serve.params import SamplingParams
from repro.serve.sampler import MAX_TOP_K, Sampler  # re-exported


# ---------------------------------------------------------------------------
# Jitted step bodies, shared across engine instances.
#
# Keyed on hashable statics (ModelConfig and Samplers are frozen
# dataclasses) so a new engine over the same config reuses compiles —
# benchmarks measure serving, not retracing. ``mesh`` is in the key
# because sharded-head tracing reads it from the ambient env at trace
# time.
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _jitted_prefill(cfg: ModelConfig, sampler: Sampler, cache_len: int,
                    mesh):
    """Dense-layout prefill (host-side admit copy) — the fallback for
    stores with no paged leaves."""
    return jax.jit(lambda p, b: api.serve_prefill(p, cfg, b, cache_len,
                                                  sampler))


@functools.lru_cache(maxsize=None)
def _jitted_prefill_paged(cfg: ModelConfig, sampler: Sampler,
                          cache_len: int, paged_mask: tuple, mesh):
    """Paged-native prefill: prompt K/V scatters into the slot's pool
    blocks INSIDE the jitted call (blocks are a traced operand); only
    the head output and the small dense leaves come back."""

    def pf(params, batch, pools, blocks):
        return api.serve_prefill_paged(params, cfg, batch, cache_len,
                                       sampler, pools=pools, blocks=blocks,
                                       paged_mask=paged_mask)

    # pools donated: install_prefill unconditionally adopts the returned
    # arrays, so the in-jit scatter aliases in place.
    return jax.jit(pf, donate_argnums=(2,))


@functools.lru_cache(maxsize=None)
def _jitted_step(cfg: ModelConfig, samplers: tuple, treedef,
                 paged_mask: tuple, mesh, spec_pallas=None):
    """THE fused ragged decode step: one jitted call per engine
    iteration, whatever mix of positions, samplers — and draft widths —
    is active.

    The trunk (``lm.decode_step``) runs ONCE over all rows with per-row
    ``positions``; paged leaves enter AS the shared pools (plus the
    ragged block table) and each layer scatters its new K/V row at its
    own position.  Then each head group — ``samplers`` is the canonical
    tuple of distinct ``device_form()`` samplers — gathers its rows from
    the shared hidden state and applies its head, all inside the same
    call.  ``rows`` (per-group row-index vectors, pow-2 padded) are
    traced operands, so WHICH rows belong to which head never retraces.

    A MULTI-TOKEN step (``toks`` (B, T > 1), ``positions`` a (B, T)
    matrix) carries any mix of window widths: speculative draft
    windows, prefill chunks, and width-1 decode rows riding along
    (their padding queries repeat their last (token, position), a cache
    no-op).  Head groups gather each row's hidden state at the LAST
    padded position — for a width-w window the padding repeats position
    w-1, so the last column IS the window's final real query (the
    next-token hidden for decode rows, the prompt's last position for a
    final prefill chunk); rows in no group (mid-prefill chunks, whose
    logits are never read) only scatter their K/V.  ``spec_pallas is
    not None`` additionally marks the speculating rows as one extra
    group verified by the comparator bank (``ops.verify_draft`` over
    their (Bs, T, D) hidden states against ``spec_cand``, -1-padded
    draft ids) — the group's output is ``(ids (Bs, T), accept (Bs,))``,
    appended after the sampler groups.
    """

    def step(params, toks, pools, denses, btab, positions, rows,
             spec_rows=None, spec_cand=None):
        leaves = [pool if m else dense
                  for m, pool, dense in zip(paged_mask, pools, denses)]
        cache = jax.tree.unflatten(treedef, leaves)
        h, new_cache = lm.decode_step(params, cfg, toks, cache, positions,
                                      block_tables=btab)
        # (B, D): each row's hidden at its window's last real query —
        # padding repeats the last (token, position), so column -1 is
        # identical to column w-1 for every width-w window.
        hl = h[:, -1] if h.ndim == 3 else h
        with jax.named_scope("head"):
            outs = tuple(s.head(params, cfg, hl[r])
                         for s, r in zip(samplers, rows))
        if spec_pallas is not None:
            w = sampler_mod._head_weight(params, cfg)
            with jax.named_scope("verify"):
                if spec_pallas == "sharded":
                    # vocab-sharded verify unit: per-position per-shard
                    # comparator + (val, idx) combine — same accept
                    # rule, O(shards) pairs per position on the wire.
                    from repro.core import reduced_softmax

                    outs = outs + (reduced_softmax.sharded_verify_draft(
                        h[spec_rows], w, spec_cand, env.current_mesh(),
                        use_pallas=cfg.use_pallas),)
                else:
                    from repro.kernels import ops as kernel_ops

                    outs = outs + (kernel_ops.verify_draft(
                        h[spec_rows], w, spec_cand,
                        use_pallas=spec_pallas),)
        new_pools, new_denses = [], []
        for m, leaf in zip(paged_mask, jax.tree.flatten(new_cache)[0]):
            new_pools.append(leaf if m else None)
            new_denses.append(None if m else leaf)
        return outs, new_pools, new_denses

    # pools are donated: write_back unconditionally replaces store.pools
    # with the returned arrays, so the in-model scatter aliases in place
    # instead of keeping a second full copy of the KV pool live per step.
    return jax.jit(step, donate_argnums=(2,))


@functools.lru_cache(maxsize=None)
def _jitted_multistep(cfg: ModelConfig, samplers: tuple, treedef,
                      paged_mask: tuple, steps: int, eos_id: int, mesh):
    """The device-resident multi-step dispatch (``host_stride``): ONE
    jitted call runs up to ``steps`` fused decode iterations inside a
    ``lax.while_loop`` (``api.serve_decode_multi``) — the sampled token
    feeds the next trunk step on device, the host only sees the final
    (B, steps) token block.

    Unlike ``_jitted_step``, the group key is the FULL sampler tuple
    (not ``device_form()``): temperature and sample_k act ON DEVICE
    here, inside ``Sampler.sample_device``.  ``steps`` and the engine's
    ``eos_id`` are static — the loop body compiles once per (config,
    sampler mix, batch bucket, table width, stride).
    """

    def run(params, toks, pools, denses, btab, positions, keys,
            emit_caps, rows):
        leaves = [pool if m else dense
                  for m, pool, dense in zip(paged_mask, pools, denses)]
        cache = jax.tree.unflatten(treedef, leaves)
        out, emitted, new_keys, new_cache = api.serve_decode_multi(
            params, cfg, toks, cache, positions, keys, emit_caps, rows,
            steps=steps, eos_id=eos_id, samplers=samplers,
            block_tables=btab)
        new_pools, new_denses = [], []
        for m, leaf in zip(paged_mask, jax.tree.flatten(new_cache)[0]):
            new_pools.append(leaf if m else None)
            new_denses.append(None if m else leaf)
        return (out, emitted, new_keys), new_pools, new_denses

    # pools donated for the same reason as _jitted_step: the while-loop
    # carry aliases the pool scatter in place across all K iterations.
    return jax.jit(run, donate_argnums=(2,))


def _to_host(out):
    """Pull a sampler head output to host: one device->host sync per
    head group, tuple-structured outputs (the k-winner bus) leaf-wise."""
    if isinstance(out, tuple):
        return tuple(np.asarray(o) for o in out)
    return np.asarray(out)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (S,) int32
    max_new_tokens: int = 16
    top_k: int = 1                     # 1 = greedy (the pure comparator)
    temperature: float = 1.0
    # the typed sampling surface; None -> synthesized at submit from the
    # legacy kwargs above.  When given, params IS the source of truth
    # (the legacy fields are mirrored from it).
    params: Optional[SamplingParams] = None
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False
    # why generation stopped: 'eos' | 'length' (max_new_tokens) |
    # 'stop' (a params.stop sequence matched the generated tail) |
    # 'max_len' (slot ran into the engine's cache ceiling — the request
    # was truncated short of its max_new_tokens) | 'cancelled'
    # (engine.cancel, e.g. a streaming client disconnected).
    finish_reason: Optional[str] = None
    # per-request sampling RNG, seeded (params.seed, or (engine seed,
    # rid)) at submit: the nth emitted token consumes the nth draw
    # regardless of scheduling (deferral, preemption), so sampled
    # generations are reproducible per request.
    rng: Optional[np.random.Generator] = None
    # per-request JAX PRNG key (raw (2,) uint32), set at submit on
    # host_stride engines only: split exactly once per EMITTED token
    # (next_key, use_key = jax.random.split(key)) whether the token was
    # sampled inside the device loop or by the host fallback — draw n
    # is a pure function of (seed, n), so generations are identical
    # across strides, batch composition and scheduling.
    prng_key: Optional[np.ndarray] = None
    # explicit Sampler; None -> resolved at submit from params plus the
    # engine's default head_mode.
    sampler: Optional[Sampler] = None
    # the prompt as submitted (preemption folds generated tokens into
    # ``prompt`` for the re-prefill; this keeps the user's original).
    orig_prompt: Optional[np.ndarray] = None
    # wall-clock stamps (time.perf_counter seconds), set by the engine:
    # submit / first prefill start / first token / final token.
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_done: Optional[float] = None


class ServeEngine:
    def __init__(self, params, cfg: ModelConfig, *, n_slots: int = 4,
                 max_len: int = 256, eos_id: int = 1,
                 head_mode: str = "reduced", kv_layout: str = "paged",
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 prefill_per_step: Optional[int] = None,
                 scheduler: str = "fused", mesh=None, seed: int = 0,
                 drafter=None, chunk_size: Optional[int] = None,
                 token_budget: Optional[int] = None,
                 host_stride: Optional[int] = None,
                 prefix_cache: bool = True,
                 attn_approx: Optional[str] = None,
                 attn_window: Optional[int] = None,
                 tp: Optional[int] = None, device=None):
        # Approximate attention: the kwargs are a convenience over the
        # ModelConfig fields (sentinel None = keep whatever the caller's
        # cfg says, so a cfg already carrying a mode isn't clobbered).
        # Being frozen-dataclass fields, the modes key every jitted
        # factory downstream automatically; 'exact' + None replace()s to
        # an EQUAL cfg, so the default engine shares jit caches — and
        # outputs — bit-identically with a pre-catalog engine.
        if attn_approx is not None or attn_window is not None:
            from repro.core import attn_approx as approx
            mode, win = approx.resolve(
                attn_approx if attn_approx is not None else cfg.attn_approx,
                attn_window if attn_window is not None else cfg.attn_window)
            cfg = dataclasses.replace(cfg, attn_approx=mode,
                                      attn_window=win)
        # Tensor parallelism (tp=N): shard the TRUNK over N devices on a
        # (1, N) 'model' mesh — Megatron column/row weight layout
        # (serve_param_specs: column-parallel QKV/up-gate, row-parallel
        # out/down, heads partitioned) with head-wise paged KV pools —
        # and upgrade the default comparator head to its SHARDED form,
        # so the only cross-shard traffic at the head is the tiny
        # (val, idx) combine, never a vocab-wide logit row.  The jitted
        # step bodies are unchanged: params/pools enter as committed
        # sharded arrays and GSPMD propagates the layout, so the ONE
        # jitted call per iteration contract is preserved.
        if tp is not None and tp < 1:
            raise ValueError(f"tp={tp}: must be >= 1 (or None)")
        if tp is not None and tp > 1:
            n_dev = len(jax.devices())
            if n_dev < tp:
                raise ValueError(
                    f"tp={tp} needs {tp} devices; only {n_dev} visible "
                    "(on a CPU host set XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={tp} "
                    "before jax initializes)")
            if mesh is None:
                from repro import compat
                mesh = compat.make_mesh((1, tp), ("data", "model"),
                                        devices=jax.devices()[:tp])
            elif int(mesh.shape.get("model", 1)) != tp:
                raise ValueError(
                    f"tp={tp} but the given mesh's 'model' axis is "
                    f"{mesh.shape.get('model', 1)}; pass ONE of tp= or "
                    "a matching mesh=")
            if head_mode in ("reduced", "fused"):
                head_mode = "sharded"
            from repro.parallel import sharding as shard_rules
            params = jax.device_put(
                params,
                shard_rules.named(
                    shard_rules.serve_param_specs(params, mesh, cfg),
                    mesh))
        # ``device`` pins a mesh-less engine (params and KV pools) to one
        # device — how a Router puts its tp=1 replicas on distinct chips.
        # Uncommitted step inputs (token ids, tables) follow the
        # committed params there.
        if device is not None:
            if mesh is not None:
                raise ValueError("pass ONE of device= (a one-device "
                                 "engine) or tp=/mesh= (a sharded one)")
            params = jax.device_put(params, device)
        self.tp = int(tp) if tp is not None else (
            int(mesh.shape.get("model", 1)) if mesh is not None else 1)
        self.params = params
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.head_mode = head_mode
        self.mesh = mesh
        if scheduler not in ("fused", "cohort"):
            raise ValueError(f"scheduler={scheduler!r}: expected 'fused' "
                             "(one step per iteration) or 'cohort' (the "
                             "PR 2 position-cohort baseline)")
        self.scheduler = scheduler
        if sampler_mod.resolve(head_mode).needs_mesh and mesh is None:
            raise ValueError(f"head_mode={head_mode!r} requires mesh=")
        self.queue: deque = deque()
        self.slots: List[Optional[Request]] = [None] * n_slots
        self.slot_pos = np.zeros(n_slots, np.int32)   # next write position
        self.admit_order: List[int] = []              # admission recency
        if prefill_per_step is not None and prefill_per_step < 1:
            raise ValueError(
                f"prefill_per_step={prefill_per_step}: must be >= 1 "
                "(or None for unlimited); 0 would serve nothing forever")
        self.prefill_per_step = prefill_per_step
        self.seed = seed
        # the draft proposer for speculative requests (spec_k > 0);
        # model-free prompt-lookup by default — any serve.spec.Drafter.
        from repro.serve.spec import PromptLookupDrafter

        self.drafter = drafter if drafter is not None \
            else PromptLookupDrafter()
        # speculation rewrites per-token cache state by position masks,
        # which only linear-attention KV supports: ring buffers lose
        # history on overwrite and recurrent state cannot rewind a
        # rejected draft.  MoE is excluded too — its capacity-dropping
        # expert routing makes a token's decode logits depend on what
        # ELSE shares the batch (draft tokens shift capacity ranks), so
        # comparator verification cannot be bit-exact against the
        # width-1 step.
        self.spec_capable = (cfg.attention_window is None and all(
            k == "attn" for k in lm.layer_types(cfg)))
        with jax.default_device(device):
            self.store = PagedKVStore(
                params, cfg, n_slots=n_slots, max_len=max_len,
                block_size=block_size, num_blocks=num_blocks,
                layout=kv_layout)
        # where this engine's params live (and so where its steps run):
        # the mesh's devices, the pinned device, or JAX's default one
        self.devices = sorted(
            set().union(*(a.devices() for a in jax.tree.leaves(params)
                          if isinstance(a, jax.Array))),
            key=lambda d: d.id)
        if self.tp > 1 and self.store.any_paged:
            # paged pools sharded HEAD-WISE over 'model': each device
            # scatters / attends only its own kv-head slice (head counts
            # that don't divide TP replicate per leaf — graceful, like
            # the weight-dim drop rule).
            self.store.shard_pools(self.mesh)
        # the approximate score functions / mask window live in the
        # PAGED decode path only — on a dense/ring layout the knob would
        # be silently ignored, which is worse than refusing.
        if (cfg.attn_approx != "exact" or cfg.attn_window is not None) \
                and not self.store.any_paged:
            raise ValueError(
                f"attn_approx={cfg.attn_approx!r} / attn_window="
                f"{cfg.attn_window!r} need the paged decode path; "
                f"kv_layout={kv_layout!r} on this config has no paged "
                "layers, so the mode would never run")
        # repro.probe.run_probe parks its latest divergence report here;
        # snapshot() (and GET /v1/stats) surfaces it as 'attn_probe'.
        self.probe_report: Optional[dict] = None
        # chunked prefill rides the same multi-token fused step as
        # speculation (repeated-padding windows, position-masked pool
        # scatters), so it carries the same capability gate — plus a
        # paged store (chunks allocate blocks incrementally) and the
        # fused scheduler (the cohort baseline has no multi-token
        # step).  Incapable configs fall back to one-shot admission.
        if chunk_size is not None and chunk_size < 1:
            raise ValueError(f"chunk_size={chunk_size}: must be >= 1 "
                             "(or None for one-shot prefill)")
        if token_budget is not None and token_budget < 1:
            raise ValueError(f"token_budget={token_budget}: must be >= 1 "
                             "(or None for unlimited)")
        self.chunk_capable = (self.spec_capable and self.store.any_paged
                              and scheduler == "fused")
        if chunk_size is not None and not self.chunk_capable:
            warnings.warn(
                f"chunk_size={chunk_size} ignored: chunked prefill needs "
                "pure linear-attention decode, a paged KV layout and "
                "scheduler='fused'; falling back to one-shot admission",
                stacklevel=2)
            chunk_size = None
        self.chunk_size = chunk_size
        self.token_budget = token_budget
        # the device loop re-runs inactive rows with their last (token,
        # position) — an idempotent K/V rewrite only for pure linear
        # attention (ring buffers would double-write, recurrent state
        # would re-advance), and only the fused scheduler has the
        # grouped multi-sampler step body.  Incapable configs fall back
        # to per-token dispatch, loudly.
        if host_stride is not None and host_stride < 1:
            raise ValueError(f"host_stride={host_stride}: must be >= 1 "
                             "(or None for per-token host dispatch)")
        self.multistep_capable = (self.spec_capable
                                  and scheduler == "fused")
        if host_stride is not None and not self.multistep_capable:
            warnings.warn(
                f"host_stride={host_stride} ignored: the device-resident "
                "decode loop needs pure linear-attention decode and "
                "scheduler='fused'; falling back to per-token dispatch",
                stacklevel=2)
            host_stride = None
        self.host_stride = host_stride
        # prefix sharing needs chunked admission: a trie hit starts
        # prefill at the SUFFIX boundary mid-prompt, which only the
        # chunk machinery can do (one-shot prefill always scatters from
        # position 0).  Engines without chunk_size just serve cold, so
        # the default True costs nothing there.
        self.prefix_cache = bool(prefix_cache) and self.chunk_size is not None
        # bounded lookahead past the queue head for length-bucketed
        # admission packing (chunked only; 1 = strict FIFO).
        self.pack_lookahead = 8
        # decode_steps counts JITTED decode calls; iterations counts
        # engine loop turns — the fused scheduler's contract is
        # decode_steps == iterations (one call whatever the position /
        # sampler mix); fused_rows counts real (non-padding) slot rows
        # served across those calls, so benches can report rows-per-step.
        # drafted/accepted count speculative draft tokens proposed /
        # verified-accepted by the comparator; acceptance_rate is their
        # running ratio (the spec-decode health metric).
        # prefill_chunks counts chunk rows served by the fused step
        # (chunked admission only); prefills still counts COMPLETED
        # prompt prefills — one-shot calls, or final chunks.
        # host_syncs counts JITTED host dispatches of any kind (one-shot
        # prefills, fused steps, multi-step loop calls) — the per-token
        # host constant host_stride amortizes; emitted_tokens counts
        # tokens through _emit_token, so emitted_tokens / host_syncs
        # (``tokens_per_dispatch`` in snapshot()) is the amortization
        # actually achieved.
        # prefix_hits / prefix_hit_tokens count admissions that mapped a
        # cached run (and the tokens they skipped); prefill_tokens counts
        # prompt tokens ACTUALLY prefilled (one-shot scatters + chunk
        # rows) — the denominator of the prefix-cache savings metric.
        # step_tokens counts the real query tokens the step programs
        # (fused or multi-step) were given, step_slots the query slots
        # they ran (padded rows x T, x the stride on the multi-step
        # path): their ratio is how full the steps were.  step_host_ns
        # is the host time of ``step()`` outside its wait for results.
        # attn_kernel_steps counts step-program iterations whose paged
        # attention ran the in-place kernel (``paged_kernel.kernel_runs``,
        # the dispatch's own rule); attn_blocks_read the pool blocks
        # their attention read over every padded row (each row's live
        # blocks under the kernel, the whole table under the
        # reference's gather); attn_blocks_table padded rows x table
        # width: their ratio is the share of the table attention read.
        self.stats = {"prefills": 0, "prefill_chunks": 0, "decode_steps": 0,
                      "iterations": 0, "fused_rows": 0, "completed": 0,
                      "deferred": 0, "preemptions": 0, "cancelled": 0,
                      "drafted": 0, "accepted": 0, "acceptance_rate": 0.0,
                      "host_syncs": 0, "emitted_tokens": 0,
                      "prefix_hits": 0, "prefix_hit_tokens": 0,
                      "prefill_tokens": 0, "step_tokens": 0,
                      "step_slots": 0, "step_host_ns": 0,
                      "attn_kernel_steps": 0, "attn_blocks_read": 0,
                      "attn_blocks_table": 0}
        self._sync_s = 0.0       # this step's wait in _sync
        # per-request TTFT samples (ms, submit -> first token), feeding
        # the percentile columns of ``snapshot()`` / GET /v1/stats.
        self._ttft_ms: List[float] = []
        # per-token event consumers: every emitted token — prefill head
        # or fused decode step — is delivered as a TokenChunk, with
        # finish_reason set on a request's final chunk.  The LLM facade
        # and the SSE server are consumers; tests register their own.
        self._consumers: List[Callable[[TokenChunk], None]] = []

    # -- event consumers -----------------------------------------------------
    def add_consumer(self, fn: Callable[[TokenChunk], None]) -> None:
        self._consumers.append(fn)

    def remove_consumer(self, fn: Callable[[TokenChunk], None]) -> None:
        self._consumers.remove(fn)

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or any(s is not None for s in self.slots)

    def snapshot(self) -> dict:
        """The counters plus derived scheduler state (JSON-ready): queue
        depth, active slots, and TTFT percentiles over every first token
        emitted so far — what ``LLM.stats`` and GET /v1/stats serve."""
        s = dict(self.stats)
        s["queue_depth"] = len(self.queue)
        s["active_slots"] = sum(sl is not None for sl in self.slots)
        s["attn_approx"] = self.cfg.attn_approx
        s["attn_window"] = self.cfg.attn_window
        if self.probe_report is not None:
            s["attn_probe"] = self.probe_report
        s["tokens_per_dispatch"] = (
            s["emitted_tokens"] / max(s["host_syncs"], 1))
        s["cow_copies"] = self.store.cow_copies
        s["shared_blocks"] = self.store.allocator.n_shared
        s["peak_in_use"] = self.store.allocator.peak_in_use
        s["live_peak"] = self.store.live_peak
        if self._ttft_ms:
            t = np.asarray(self._ttft_ms)
            s["ttft_ms_p50"] = float(np.percentile(t, 50))
            s["ttft_ms_p99"] = float(np.percentile(t, 99))
        else:
            s["ttft_ms_p50"] = s["ttft_ms_p99"] = None
        return s

    # -- queue management ----------------------------------------------------
    @functools.partial(jax.profiler.annotate_function, name="serve.submit")
    def submit(self, req: Request):
        if req.params is None:
            # legacy surface: synthesize the typed params from the loose
            # kwargs so every downstream consumer sees ONE source of truth
            req.params = SamplingParams(max_new_tokens=req.max_new_tokens,
                                        temperature=req.temperature,
                                        top_k=req.top_k)
        else:
            # params given: mirror into the legacy fields (engine
            # internals and old call sites read max_new_tokens et al.)
            req.max_new_tokens = req.params.max_new_tokens
            req.top_k = req.params.top_k
            req.temperature = req.params.temperature
        if req.sampler is None:
            req.sampler = sampler_mod.resolve(
                req.params, cfg=self.cfg,
                default_head_mode=self.head_mode)
        else:
            req.sampler.validate(self.cfg)
        if req.sampler.needs_mesh and self.mesh is None:
            raise ValueError(f"{req.sampler} requires an engine mesh=")
        if req.params.attn_approx is not None \
                and req.params.attn_approx != self.cfg.attn_approx:
            # attention mode is engine-wide (ONE fused step serves every
            # slot) — a per-request switch would need per-mode step
            # compilation and batch splitting.  The param is a contract
            # check, not a dispatch knob.
            raise ValueError(
                f"params.attn_approx={req.params.attn_approx!r} but this "
                f"engine runs attn_approx={self.cfg.attn_approx!r}; "
                "attention mode is engine-wide — construct the engine "
                "with attn_approx= (or drop the param to accept any)")
        if self.host_stride is not None:
            if req.params.spec_k > 0:
                raise ValueError(
                    f"spec_k={req.params.spec_k} and host_stride="
                    f"{self.host_stride} are mutually exclusive: both "
                    "amortize the per-token host round-trip and the "
                    "device loop has no draft-verify group (composing "
                    "them is future work)")
            if req.params.n_candidates > 0:
                raise ValueError(
                    f"n_candidates={req.params.n_candidates} is not "
                    "available on a host_stride engine: the device loop "
                    "consumes the k-winner bus on device and ships only "
                    "sampled token ids")
            # sharded heads ride the device loop fine: the engine wraps
            # every dispatch in env.use_mesh, so the head's shard_map
            # traces against the ambient mesh inside the while_loop
            # body too (the submit-time needs_mesh/mesh check above
            # already guaranteed a mesh exists).
            if type(req.sampler).sample_device is Sampler.sample_device:
                raise ValueError(
                    f"{req.sampler} has no device sampling form "
                    "(Sampler.sample_device) and cannot ride a "
                    "host_stride engine")
            if req.prng_key is None:
                # the keyed analogue of req.rng: params.seed pins the
                # stream, (engine seed, rid) keeps requests distinct.
                base = (jax.random.PRNGKey(req.params.seed)
                        if req.params.seed is not None
                        else jax.random.fold_in(
                            jax.random.PRNGKey(self.seed), req.rid))
                req.prng_key = np.asarray(base, np.uint32)
        if req.params.spec_k > 0:
            # params validated the sampling law; the ENGINE must also be
            # able to verify: comparator head, rewindable cache state,
            # and the fused scheduler (the cohort baseline predates the
            # multi-token step).
            if not (isinstance(req.sampler, sampler_mod.Greedy)
                    and req.sampler.head_mode in ("reduced", "fused",
                                                  "sharded")):
                raise ValueError(
                    f"spec_k={req.params.spec_k} requires the reduced "
                    f"comparator head (engine head_mode="
                    f"{self.head_mode!r} resolved to {req.sampler})")
            if not self.spec_capable:
                raise ValueError(
                    f"spec_k={req.params.spec_k}: speculative decoding "
                    "needs pure linear-attention decode (no sliding "
                    "window or recurrent state — rejected drafts cannot "
                    "be rewound; no capacity-dropping MoE routing — "
                    "draft tokens would shift expert capacity and break "
                    f"bit-exactness); the {self.cfg.family!r} config "
                    "does not qualify")
            if self.scheduler != "fused":
                raise ValueError(
                    f"spec_k={req.params.spec_k} requires "
                    "scheduler='fused' (the cohort baseline has no "
                    "multi-token step)")
        if len(req.prompt) > self.max_len - 1:
            raise ValueError(
                f"prompt of {len(req.prompt)} tokens exceeds max_len-1="
                f"{self.max_len - 1}")
        # a request fits iff prompt + max_new <= max_len (the t-th token
        # lands at slot_pos = prompt + t - 1, and the max_len-1 ceiling
        # is only checked when max_new_tokens hasn't already finished it)
        if len(req.prompt) + req.max_new_tokens > self.max_len:
            warnings.warn(
                f"request rid={req.rid}: prompt ({len(req.prompt)} tokens) "
                f"+ max_new_tokens ({req.max_new_tokens}) exceeds "
                f"max_len={self.max_len}; generation will stop early "
                "with finish_reason='max_len'", stacklevel=2)
        if req.rng is None:
            # params.seed pins the request's private RNG stream; the
            # (engine seed, rid) default keeps distinct requests distinct
            req.rng = np.random.default_rng(
                req.params.seed if req.params.seed is not None
                else [self.seed, req.rid])
        if req.orig_prompt is None:
            req.orig_prompt = np.asarray(req.prompt, np.int32).copy()
        req.t_submit = time.perf_counter()
        self.queue.append(req)

    def cancel(self, req: Request) -> bool:
        """Abort an unfinished request: free its slot's blocks (or drop
        it from the queue) and finish it with ``finish_reason=
        'cancelled'``.  The serving frontend calls this when a streaming
        client disconnects — otherwise the request would decode to
        max_new_tokens holding a slot nobody reads."""
        if req.done:
            return False
        for i, s in enumerate(self.slots):
            if s is req:
                self._release_slot(i)
                break
        else:
            try:
                self.queue.remove(req)
            except ValueError:
                return False              # unknown request
        req.finish_reason = "cancelled"
        req.t_done = time.perf_counter()
        req.done = True
        self.stats["cancelled"] += 1
        return True

    def _free_slots(self):
        return [i for i, s in enumerate(self.slots) if s is None]

    def _prefilling(self, i: int) -> bool:
        """Whether slot ``i`` is mid-chunked-prefill: its write cursor
        (``slot_pos``) has not yet covered its prompt.  One-shot
        admission scatters the whole prompt before the slot is visible,
        so this is only ever True under ``chunk_size``."""
        req = self.slots[i]
        return req is not None and int(self.slot_pos[i]) < len(req.prompt)

    def _admit(self):
        """Prefill queued requests into free slots (continuous batching).

        At most ``prefill_per_step`` admissions per engine iteration so
        prefill work cannot starve in-flight decodes; admission defers
        when the block pool cannot cover the prompt plus one decode
        block.  Deferral stops at the QUEUE HEAD — later (shorter)
        requests never jump a deferred head, so FIFO admission is
        starvation-free.  Paged stores admit natively: blocks are
        allocated first and the jitted prefill scatters the prompt K/V
        straight into them.

        Under ``chunk_size`` admission only ASSIGNS the slot (and
        reserves the first chunk's blocks) — the prompt is scattered
        chunk-by-chunk by the fused step itself (``_plan_chunks`` /
        ``_decode_rows``), so no separate jitted prefill call ever runs.
        """
        if self.chunk_size is not None:
            return self._admit_chunked()
        budget = self.prefill_per_step
        for i in self._free_slots():
            if not self.queue or budget == 0:
                break
            req = self.queue[0]
            S = len(req.prompt)
            if not self.store.can_admit(S):
                self.stats["deferred"] += 1
                break
            self.queue.popleft()
            if req.t_admit is None:       # re-prefill keeps the first stamp
                req.t_admit = time.perf_counter()
            plen = self.store.prefill_len(S)
            batch = {"tokens": jnp.asarray(req.prompt, jnp.int32)[None]}
            dev = req.sampler.device_form()
            with env.use_mesh(self.mesh):
                if self.store.any_paged:
                    blocks = self.store.alloc_blocks(i, S)
                    # install_prefill COW rule: the jitted prefill
                    # scatters [0, S) into donated pools, so any shared
                    # cover would have to copy HERE.  One-shot slots
                    # only ever hold the fresh blocks just allocated
                    # (prefix adoption is chunked-only), so this is the
                    # enforced no-op form of the invariant.
                    self.store.cow_for_write(i, 0, S - 1)
                    fn = _jitted_prefill_paged(
                        self.cfg, dev, plen,
                        tuple(self.store.paged_mask), self.mesh)
                    out, new_pools, dense_leaves = fn(
                        self.params, batch, self.store.pools,
                        jnp.asarray(blocks, jnp.int32))
                    self.store.install_prefill(i, new_pools, dense_leaves)
                else:
                    fn = _jitted_prefill(self.cfg, dev, plen, self.mesh)
                    out, cache1 = fn(self.params, batch)
                    self.store.admit(i, jax.tree.flatten(cache1)[0], S)
            self.stats["prefills"] += 1
            self.stats["prefill_tokens"] += S
            self.stats["host_syncs"] += 1
            self.slots[i] = req
            self.slot_pos[i] = S
            self.admit_order.append(i)
            self._emit(i, req, _to_host(out), 0)
            if budget is not None:
                budget -= 1

    def _admit_chunked(self):
        """Chunked admission: assign free slots and reserve each
        request's FIRST chunk cover; the fused step scatters the chunks.

        The queue HEAD is always offered first — deferral stops there,
        so the FIFO starvation-freedom of one-shot admission carries
        over unchanged.  Admissions AFTER the head within one iteration
        are packed by LENGTH BUCKET (t2t bucketing-by-length): a
        bounded lookahead (``pack_lookahead``) prefers the first
        admissible queued request whose pow-2 first-chunk width matches
        the bucket this iteration is already paying for, so one short
        prompt admitted beside a long one does not widen T for every
        row.  A skipped request keeps (or reaches) the head position
        and is admitted next iteration at the latest.
        """
        budget = self.prefill_per_step
        bucket = None
        for i in self._free_slots():
            if not self.queue or budget == 0:
                break
            if not self.store.can_admit(len(self.queue[0].prompt),
                                        self.chunk_size):
                self.stats["deferred"] += 1
                break
            pick = 0
            if bucket is not None:
                for j in range(min(self.pack_lookahead, len(self.queue))):
                    cand = self.queue[j]
                    if (_pow2(min(self.chunk_size, len(cand.prompt)))
                            == bucket
                            and self.store.can_admit(len(cand.prompt),
                                                     self.chunk_size)):
                        pick = j
                        break
            req = self.queue[pick]
            del self.queue[pick]
            if req.t_admit is None:       # re-prefill keeps the first stamp
                req.t_admit = time.perf_counter()
            hit = 0
            if self.prefix_cache and req.params.prefix_cache:
                # map the longest cached whole-block run into the slot's
                # table; chunked prefill then starts at the SUFFIX
                # boundary (positions are per-row already, so nothing
                # downstream changes).  Adoption precedes the reserve so
                # eviction under pressure cannot reclaim the run first.
                hit = self.store.adopt_prefix(i, req.prompt)
                if hit:
                    self.stats["prefix_hits"] += 1
                    self.stats["prefix_hit_tokens"] += hit
            width = min(self.chunk_size, len(req.prompt) - hit)
            bucket = _pow2(width)
            # reserve the first chunk's cover NOW so this iteration's
            # later can_admit checks see the honest free count
            self.store.ensure_capacity(i, hit + width - 1, write_start=hit)
            self.slots[i] = req
            self.slot_pos[i] = hit        # write cursor: suffix starts here
            self.admit_order.append(i)
            if budget is not None:
                budget -= 1

    def _preempt_youngest(self, keep: int) -> bool:
        """Pool exhausted mid-decode: push the most recently admitted slot
        (except ``keep``) back to the queue, freeing its blocks.  The
        request re-prefills later with its tokens so far as the prompt."""
        for i in reversed(self.admit_order):
            if i == keep or self.slots[i] is None:
                continue
            req = self.slots[i]
            # fold emitted tokens into the prompt; ``generated`` keeps the
            # full emission history (re-prefill continues exactly after
            # it).  Fold from ORIG_PROMPT, not req.prompt: after a second
            # preemption req.prompt already contains the first fold's
            # tokens and concatenating generated again would duplicate
            # them — a silently corrupted re-prefill context.
            req.prompt = np.concatenate(
                [np.asarray(req.orig_prompt, np.int32),
                 np.asarray(req.generated, np.int32)])
            self._release_slot(i)
            self.queue.appendleft(req)
            self.stats["preemptions"] += 1
            return True
        return False

    # -- main loop ------------------------------------------------------------
    def step(self):
        """One engine iteration: admit, then ONE fused ragged step over
        every active slot — decode rows, speculative windows and
        prefill-chunk rows in the same jitted call
        (``scheduler='cohort'`` partitions by (position, head) first —
        the position-cohort baseline).

        Traced as the host span ``serve.step`` (args: the dispatched
        shape) with the children ``serve.admit``, ``serve.plan``,
        ``serve.operands``, ``serve.dispatch``, ``serve.sync`` and
        ``serve.emit``."""
        t0 = time.perf_counter()
        self._sync_s = 0.0
        with TraceAnnotation("serve.step") as span:
            busy, shape = self._iterate()
            if shape:
                span.set_metadata(**shape)
        self.stats["step_host_ns"] += round(
            (time.perf_counter() - t0 - self._sync_s) * 1e9)
        return busy

    def _iterate(self):
        """``step``'s body: ``(more work, the dispatched shape)``."""
        self.stats["iterations"] += 1
        with TraceAnnotation("serve.admit"):
            self._admit()
        active = [i for i, s in enumerate(self.slots) if s is not None]
        if not active:
            if self.queue and not self.store.can_admit(
                    len(self.queue[0].prompt), self.chunk_size):
                # nothing is running, so every block is free — if the head
                # request still doesn't fit it never will: fail loudly
                # instead of spinning to max_iters with served=0.
                req = self.queue[0]
                raise MemoryError(
                    f"request rid={req.rid} ({len(req.prompt)}-token "
                    f"prompt) can never be admitted: pool of "
                    f"{self.store.allocator.num_blocks} x "
                    f"{self.store.block_size}-token blocks is too small")
            return bool(self.queue), None
        # capacity pass at each slot's OWN position; a later slot's
        # ensure may have PREEMPTED an earlier accepted one (keep= only
        # shields the current slot): re-validate afterwards.
        with TraceAnnotation("serve.plan"):
            active = [i for i in active
                      if self._ensure_blocks(i, int(self.slot_pos[i]))]
            active = [i for i in active if self.slots[i] is not None]
        if not active:
            return True, None
        if self.scheduler == "cohort":
            parts: Dict[tuple, list] = {}
            for i in active:
                dev = self.slots[i].sampler.device_form()
                parts.setdefault((int(self.slot_pos[i]), repr(dev)),
                                 []).append(i)
            shapes = [self._decode_rows(parts[key]) for key in sorted(parts)]
            shape = {k: sum(s[k] for s in shapes) for k in shapes[0]}
            shape["T"] = max(s["T"] for s in shapes)
            return True, shape
        if (self.host_stride is not None
                and not any(self._prefilling(i) for i in active)):
            # the device-resident multi-step dispatch: one host sync
            # for up to host_stride tokens per row.  Iterations with a
            # mid-prefill slot fall back to the legacy single step (the
            # loop has no chunk rows) — still keyed, so generations
            # stay stride-invariant; admission/preemption above already
            # synchronized at this stride boundary.
            return True, self._decode_multi(active)
        return True, self._decode_rows(active)

    def _sync(self, outs) -> list:
        """Wait for a dispatch's outputs on the host (``serve.sync``)."""
        t = time.perf_counter()
        with TraceAnnotation("serve.sync"):
            host = [_to_host(o) for o in outs]
        self._sync_s += time.perf_counter() - t
        return host

    def _count_step(self, n_real: int, n_padded: int, T: int,
                    n_chunk: int, tokens: int, slots: int) -> dict:
        """Count a dispatched step program; its shape for the span."""
        self.stats["decode_steps"] += 1
        self.stats["host_syncs"] += 1
        self.stats["fused_rows"] += n_real
        self.stats["step_tokens"] += tokens
        self.stats["step_slots"] += slots
        return {"rows": n_real, "padded_rows": n_padded, "T": T,
                "chunk_rows": n_chunk, "tokens": tokens}

    def _count_attention(self, last_pos, nb: int, T: int,
                         iterations: int = 1) -> None:
        """Count the paged attention of ``iterations`` step-program
        iterations of query width ``T`` over a table ``nb`` wide, whose
        padded rows sit at ``last_pos`` (their largest query position;
        one entry per row per iteration)."""
        last_pos = np.asarray(last_pos)
        self.stats["attn_blocks_table"] += last_pos.size * nb
        if paged_kernel.kernel_runs(T, use_pallas=self.cfg.use_pallas,
                                    attn_approx=self.cfg.attn_approx,
                                    mesh=self.mesh):
            self.stats["attn_kernel_steps"] += iterations
            self.stats["attn_blocks_read"] += int(paged_kernel.live_blocks(
                last_pos, self.store.block_size, nb).sum())
        else:
            self.stats["attn_blocks_read"] += last_pos.size * nb

    def _propose(self, i: int) -> list:
        """Draft tokens for slot ``i`` this step (possibly none): ask the
        Drafter for up to the request's remaining speculation budget,
        then shrink the window to what the cache ceiling and the free
        block pool can actually hold — speculation never preempts a
        neighbour, it just drafts less."""
        req = self.slots[i]
        k = req.params.spec_k
        if k <= 0 or self.scheduler != "fused" or self._prefilling(i):
            return []
        pos = int(self.slot_pos[i])
        # a draft window writes K/V at pos..pos+k and can emit up to
        # k+1 tokens: clamp to the remaining token budget and to the
        # max_len-1 cache ceiling.
        k = min(k, req.max_new_tokens - len(req.generated) - 1,
                self.max_len - 1 - pos)
        if k < 1:
            return []
        history = [int(t) for t in req.orig_prompt] \
            + [int(t) for t in req.generated]
        drafts = []
        for t in self.drafter.propose(history, k)[:k]:
            if not 0 <= int(t) < self.cfg.vocab_size:
                break             # a bad drafter id can never be accepted
            drafts.append(int(t))
        while drafts and not self.store.can_grow(i, pos + len(drafts),
                                                 write_start=pos):
            drafts.pop()
        if drafts and not self.store.ensure_capacity(i, pos + len(drafts),
                                                     write_start=pos):
            return []             # lost a race with another slot's growth
        return drafts

    def _plan_chunks(self, rows: List[int], n_decode_tokens: int) -> dict:
        """Plan this iteration's prefill-chunk windows: ``{slot: (start,
        width)}`` for every mid-prefill slot in ``rows``.

        Width = min(chunk_size, remaining prompt), then shrunk to the
        per-iteration ``token_budget`` (decode rows are always served;
        the budget throttles chunk width only) and to the free block
        pool (``can_grow`` — a chunk narrows rather than preempt a
        neighbour, exactly like a draft window).  Oldest-admitted slots
        plan first and every prefilling slot keeps >= 1 token, so
        head-of-line prefill progress is monotone whatever the budget.
        """
        chunks: dict = {}
        pre = [i for i in rows if self._prefilling(i)]
        if not pre:
            return chunks
        recency = {slot: n for n, slot in enumerate(self.admit_order)}
        pre.sort(key=lambda j: recency.get(j, 0))
        avail = None
        if self.token_budget is not None:
            avail = max(self.token_budget - n_decode_tokens, len(pre))
        for n, i in enumerate(pre):
            start = int(self.slot_pos[i])
            w = min(self.chunk_size, len(self.slots[i].prompt) - start)
            if avail is not None:
                later = len(pre) - n - 1       # reserve 1 token each
                w = max(1, min(w, avail - later))
                avail -= w
            while w > 1 and not self.store.can_grow(i, start + w - 1,
                                                    write_start=start):
                w -= 1
            self.store.ensure_capacity(i, start + w - 1, write_start=start)
            chunks[i] = (start, w)
        return chunks

    def _decode_rows(self, rows: List[int]):
        """One fused jitted decode call over the given slot rows — ragged
        positions, mixed samplers, per-row draft widths.

        Batch and block-view sizes are bucketed to powers of two so
        decode compiles O(log n_slots * log max_blocks) shapes, not one
        per (batch, seq-length) pair.  Padding rows duplicate row 0
        (identical compute; the duplicate write lands the same value on
        the same cache cell); padded block-table columns repeat a block
        the row owns, past its position, so the per-row kv_pos<=pos mask
        discards them.  Head groups (one per distinct ``device_form()``)
        partition the padded rows; their pow-2-padded row-index vectors
        are traced operands of the ONE jitted call.

        Rows with draft tokens this step (``_propose``) or a pending
        prefill chunk (``_plan_chunks``) widen the call to T = pow2(max
        window width): a draft row carries its last token plus drafts
        at consecutive positions and joins the COMPARATOR-VERIFY group
        (``ops.verify_draft`` inside the same jitted call); a CHUNK row
        carries the next ``chunk_size`` prompt tokens at their absolute
        positions, attends over its earlier chunks through the block
        table (same in-window causal rule: kv_pos <= pos[b, t]) and
        joins NO head group until its FINAL chunk, whose last position
        feeds the row's sampler head and emits the request's first
        token; every other row rides along at width 1, padding queries
        repeating its last (token, position) — a cache no-op.
        The verified rows then emit their whole accepted run (plus the
        comparator's correction token) host-side, token by token, so
        stop/eos/length/consumer semantics are IDENTICAL to
        non-speculative decoding — a mid-run hit truncates the run and
        the slot position simply never advances over the rejected tail
        (``store.rewind`` returns surplus blocks).
        """
        n_real = len(rows)
        with TraceAnnotation("serve.plan"):
            drafts = {i: self._propose(i) for i in rows}
            n_decode_tokens = sum(1 + len(drafts[i]) for i in rows
                                  if not self._prefilling(i))
            chunks = self._plan_chunks(rows, n_decode_tokens)
        with TraceAnnotation("serve.operands"):
            fn, args, where, order, T, padded = self._step_operands(
                rows, drafts, chunks)
        with TraceAnnotation("serve.dispatch"):
            with env.use_mesh(self.mesh):
                outs, new_pools, new_denses = fn(*args)
            self.store.write_back(
                rows, new_pools,
                [None if d is None else d[:, :n_real] for d in new_denses])
        shape = self._count_step(
            n_real, len(padded), T, len(chunks),
            sum(chunks[i][1] if i in chunks else 1 + len(drafts[i])
                for i in rows),
            len(padded) * T)
        # one device->host sync per head group, not per slot
        host_outs = self._sync(outs)
        host = dict(zip(order, host_outs))
        spec_host = host_outs[len(order)] if len(outs) > len(order) else None
        with TraceAnnotation("serve.emit"):
            self._emit_rows(rows, where, chunks, drafts, host, spec_host)
        return shape

    def _step_operands(self, rows, drafts, chunks):
        """The fused step's program and operands for ``rows``: host
        arrays, block table and row sets, sent to the device.  Returns
        ``(fn, args, where, order, T, padded)``; ``where[r]`` is padded
        row ``r``'s (head group, offset)."""
        n_real = len(rows)
        width = max([1 + len(drafts[i]) for i in rows]
                    + [w for _, w in chunks.values()])
        T = _pow2(width)
        padded = rows + [rows[0]] * (_pow2(n_real) - n_real)
        groups: Dict[Sampler, list] = {}
        spec_group: list = []            # padded-row indices that verify
        spec_modes = set()
        where = []                       # row r -> (its group, offset)
        for r, i in enumerate(padded):
            ch = chunks.get(i)
            if ch is not None and ch[0] + ch[1] < len(self.slots[i].prompt):
                # mid-prefill chunk: scatters K/V only — its logits are
                # never materialized, so it joins NO head group.
                where.append((None, None))
            elif T > 1 and drafts[i]:
                where.append((None, len(spec_group)))
                spec_group.append(r)
                spec_modes.add(self.slots[i].sampler.head_mode)
            else:
                # decode rows AND final prefill chunks: the row's head
                # reads its window's last real position (the padding
                # convention makes that the last padded column).
                dev = self.slots[i].sampler.device_form()
                lst = groups.setdefault(dev, [])
                where.append((dev, len(lst)))
                lst.append(r)
        order = sampler_mod.canonical_order(groups)
        row_sets = tuple(
            jnp.asarray(groups[dev] + [groups[dev][0]]
                        * (_pow2(len(groups[dev])) - len(groups[dev])),
                        jnp.int32)
            for dev in order)
        toks = np.zeros((len(padded), T), np.int32)
        posm = np.zeros((len(padded), T), np.int32)
        for r, i in enumerate(padded):
            ch = chunks.get(i)
            if ch is not None:
                # prefill chunk: the next `w` prompt tokens at their
                # absolute positions — history (earlier chunks) is
                # visible through the block table, the in-window causal
                # mask is the same kv_pos <= pos[b, t] rule.
                base, w = ch
                win = [int(t) for t in
                       self.slots[i].prompt[base:base + w]]
            else:
                win = [self.slots[i].generated[-1]] + drafts[i]
                base = int(self.slot_pos[i])
                w = len(win)
            toks[r, :w] = win
            toks[r, w:] = win[-1]        # repeat last (token, position):
            posm[r, :w] = base + np.arange(w)
            posm[r, w:] = base + w - 1   # identical value, identical cell
        btab = self.store.block_table(padded, posm[:, -1])
        if btab is not None:
            self._count_attention(posm[:, -1], btab.shape[1], T)
        denses = self.store.dense_sub(padded)
        spec_pallas = spec_rows_op = spec_cand_op = None
        if spec_group:
            # 'sharded' routes the verify bank through the per-shard
            # comparator + combine; otherwise a bool picks Pallas vs ref.
            spec_pallas = ("sharded" if "sharded" in spec_modes
                           else bool(self.cfg.use_pallas)
                           or "fused" in spec_modes)
            sg = spec_group + [spec_group[0]] \
                * (_pow2(len(spec_group)) - len(spec_group))
            spec_rows_op = jnp.asarray(sg, jnp.int32)
            cand = np.full((len(sg), T - 1), -1, np.int32)
            for o, r in enumerate(sg):
                d = drafts[padded[r]]
                cand[o, :len(d)] = d
            spec_cand_op = jnp.asarray(cand)
        fn = _jitted_step(self.cfg, tuple(order), self.store.treedef,
                          tuple(self.store.paged_mask), self.mesh,
                          spec_pallas)
        # (B,) positions at T == 1 (the pure-decode fast path, same
        # compiled shapes as ever); (B, T) whenever any window — draft
        # or chunk — widens the step.
        args = (self.params, jnp.asarray(toks), self.store.pools, denses,
                None if btab is None else jnp.asarray(btab),
                jnp.asarray(posm if T > 1 else posm[:, 0]), row_sets)
        if spec_group:
            args += (spec_rows_op, spec_cand_op)
        return fn, args, where, order, T, padded

    def _emit_rows(self, rows, where, chunks, drafts, host, spec_host):
        """Emit what a fused step produced for its real ``rows``:
        advance chunk cursors (a final chunk emits the first token),
        drain verified draft runs, emit decode tokens."""
        for r, i in enumerate(rows):
            dev, off = where[r]
            req = self.slots[i]
            if i in chunks:
                # prefill chunk served: advance the write cursor over
                # it.  A FINAL chunk is the moment one-shot admission
                # called "prefill done": the head output at the
                # prompt's last position emits the first token.
                start, w = chunks[i]
                self.slot_pos[i] = start + w
                self.stats["prefill_chunks"] += 1
                self.stats["prefill_tokens"] += w
                if start + w == len(req.prompt):
                    self.stats["prefills"] += 1
                    self._emit(i, req, host[dev], off)
                continue
            if dev is None:
                # speculative row: the comparator verified the whole
                # draft window — emit the accepted run plus the
                # correction token, one at a time (stop/eos/length fire
                # exactly as they would have, mid-run included).
                ids, acc = spec_host
                w = len(drafts[i])
                m = min(int(acc[off]), w)
                self.stats["drafted"] += w
                self.stats["accepted"] += m
                for tok in ids[off, :m + 1]:
                    self.slot_pos[i] += 1
                    self._emit_token(i, req, int(tok))
                    if req.done:
                        break
                if not req.done:
                    # O(1) rewind of the rejected tail: the position
                    # never advanced over it (stale rows are invisible
                    # behind the kv_pos<=pos masks); surplus whole
                    # blocks go back to the free list.
                    self.store.rewind(i, int(self.slot_pos[i]))
            else:
                self.slot_pos[i] += 1
                self._emit(i, req, host[dev], off)
        if self.stats["drafted"]:
            self.stats["acceptance_rate"] = (
                self.stats["accepted"] / self.stats["drafted"])

    def _decode_multi(self, rows: List[int]):
        """One device-resident multi-step dispatch over the given slot
        rows: up to ``host_stride`` fused iterations inside a single
        jitted ``lax.while_loop``, then a host drain of the returned
        (B, K) token block through the ordinary per-token emission path.

        Every stop condition the device can evaluate is folded into a
        per-row EMIT CAP before dispatch: the remaining
        ``max_new_tokens``, the ``max_len - 1`` cache ceiling, and
        block-table capacity (grown here up to the cap's last write,
        shrinking the cap instead of preempting a neighbour — same
        policy as draft/chunk windows).  The eos id halts a row inside
        the loop (the eos token itself is emitted).  Stop SEQUENCES are
        matched on the host during the drain: a match finishes the
        request mid-block and the remaining tokens are TRIMMED — never
        emitted, their KV invisible behind the position masks and their
        surplus blocks rewound O(1).  That is the bounded-lag contract:
        at most ``host_stride - 1`` tokens of wasted device work past a
        stop, zero tokens of wasted emission.

        Groups key on the FULL sampler (temperature acts on device via
        ``sample_device``); per-row PRNG keys ride the loop carry and
        the advanced keys are adopted afterwards, so draw n stays a
        pure function of (request seed, n) whatever the stride.
        """
        K = self.host_stride
        caps: Dict[int, int] = {}
        with TraceAnnotation("serve.plan"):
            for i in rows:
                req = self.slots[i]
                pos = int(self.slot_pos[i])
                cap = max(1, min(K, req.max_new_tokens - len(req.generated),
                                 self.max_len - 1 - pos))
                while cap > 1 and not self.store.can_grow(
                        i, pos + cap - 1, write_start=pos):
                    cap -= 1
                if cap > 1 and not self.store.ensure_capacity(
                        i, pos + cap - 1, write_start=pos):
                    cap = 1       # lost a race; ``pos`` itself is covered
                caps[i] = cap
        with TraceAnnotation("serve.operands"):
            fn, args, padded = self._multi_operands(rows, caps)
        n_real = len(rows)
        with TraceAnnotation("serve.dispatch"):
            with env.use_mesh(self.mesh):
                (out, emitted, new_keys), new_pools, new_denses = fn(*args)
            self.store.write_back(
                rows, new_pools,
                [None if d is None else d[:, :n_real] for d in new_denses])
        out_h, emitted_h, keys_h = self._sync((out, emitted, new_keys))
        btab = args[4]
        if btab is not None:
            # the loop ran while any row emitted; a row attends at its
            # start position plus the tokens it has emitted so far
            its = int(emitted_h.max())
            pos0 = np.asarray([int(self.slot_pos[i]) for i in padded])
            last = pos0[:, None] + np.minimum(np.arange(its)[None],
                                              emitted_h[:, None])
            self._count_attention(last, btab.shape[1], 1, its)
        shape = self._count_step(n_real, len(padded), K, 0,
                                 int(emitted_h[:n_real].sum()),
                                 len(padded) * K)
        with TraceAnnotation("serve.emit"):
            self._drain_multi(rows, out_h, emitted_h, keys_h)
        return shape

    def _multi_operands(self, rows, caps):
        """The multi-step program and its operands for ``rows`` with
        per-row emit ``caps``: ``(fn, args, padded)``."""
        K = self.host_stride
        n_real = len(rows)
        padded = rows + [rows[0]] * (_pow2(n_real) - n_real)
        groups: Dict[Sampler, list] = {}
        for r, i in enumerate(padded):
            groups.setdefault(self.slots[i].sampler, []).append(r)
        order = sampler_mod.canonical_order(groups)
        row_sets = tuple(
            jnp.asarray(groups[s] + [groups[s][0]]
                        * (_pow2(len(groups[s])) - len(groups[s])),
                        jnp.int32)
            for s in order)
        toks = np.asarray([self.slots[i].generated[-1] for i in padded],
                          np.int32)
        pos_arr = np.asarray([int(self.slot_pos[i]) for i in padded],
                             np.int32)
        keys = np.stack([self.slots[i].prng_key for i in padded]
                        ).astype(np.uint32)
        emit_caps = np.zeros(len(padded), np.int32)
        emit_caps[:n_real] = [caps[i] for i in rows]
        # padding duplicates never emit (cap 0), but their block table
        # still covers their (frozen) write position via the real row's.
        last_write = pos_arr + np.asarray([caps[i] for i in padded],
                                          np.int32) - 1
        btab = self.store.block_table(padded, last_write)
        denses = self.store.dense_sub(padded)
        fn = _jitted_multistep(
            self.cfg, tuple(order), self.store.treedef,
            tuple(self.store.paged_mask), K,
            -1 if self.eos_id is None else int(self.eos_id), self.mesh)
        args = (self.params, jnp.asarray(toks), self.store.pools, denses,
                None if btab is None else jnp.asarray(btab),
                jnp.asarray(pos_arr), jnp.asarray(keys),
                jnp.asarray(emit_caps), row_sets)
        return fn, args, padded

    def _drain_multi(self, rows, out_h, emitted_h, keys_h):
        """Emit a multi-step block row by row, trimming at a stop."""
        for r, i in enumerate(rows):
            req = self.slots[i]
            if req is None:
                # a consumer cancelled this slot while an earlier row
                # drained: its undrained tokens are simply dropped (the
                # blocks already went back to the free list).
                continue
            req.prng_key = keys_h[r].copy()
            for tok in out_h[r, :int(emitted_h[r])]:
                self.slot_pos[i] += 1
                self._emit_token(i, req, int(tok))
                if req.done:
                    # stop/eos/length/cancel fired mid-block: trim the
                    # rest of the drained block (bounded-lag contract)
                    break
            if not req.done:
                # surplus cover past the (possibly shrunk) cursor back
                # to the free list — cheap, and keeps the invariant
                # that a live slot covers exactly its next write.
                self.store.rewind(i, int(self.slot_pos[i]))

    def _ensure_blocks(self, i: int, pos: int) -> bool:
        """Grow slot i's block table to cover ``pos``; preempt the
        youngest other slot if the pool is dry."""
        if self.slots[i] is None:      # preempted earlier this iteration
            return False
        while not self.store.ensure_capacity(i, pos):
            if not self._preempt_youngest(keep=i):
                raise MemoryError(
                    "paged KV pool too small for a single sequence: "
                    f"pos={pos} block_size={self.store.block_size} "
                    f"num_blocks={self.store.allocator.num_blocks}")
        return self.slots[i] is not None

    def _release_slot(self, i: int):
        req = self.slots[i]
        publish = None
        if self.prefix_cache and req is not None and req.params.prefix_cache:
            # the slot's K/V rows [0, slot_pos) hold exactly this token
            # history (original prompt ++ emissions — re-prefills and
            # spec rewinds preserve this), so the full-block run is
            # publishable whatever path ends here: completion, cancel,
            # or preemption.  A preempted request then re-matches its
            # own run at re-admission and re-prefills only the tail.
            publish = np.concatenate(
                [np.asarray(req.orig_prompt, np.int32),
                 np.asarray(req.generated, np.int32)]
            )[:int(self.slot_pos[i])]
        self.store.release(i, publish_tokens=publish)
        self.slots[i] = None
        self.admit_order.remove(i)

    def _emit(self, i: int, req: Request, host_out, off: int):
        """One token emission off a sampler head output: pick on the
        host (plus the optional candidate bus), then the shared
        emission path.  On a host_stride engine the pick is KEYED —
        the same jax ops ``sample_device`` runs in the device loop,
        consuming one split of the request's key — so tokens emitted
        by this fallback (prefill heads, chunked-prefill iterations)
        are bit-identical to what the device loop would have sampled."""
        if self.host_stride is not None:
            nk, uk = jax.random.split(jnp.asarray(req.prng_key))
            tok = req.sampler.pick_keyed(host_out, off, uk)
            req.prng_key = np.asarray(nk, np.uint32)
        else:
            tok = req.sampler.pick(host_out, off, req.rng)
        cands = None
        if self._consumers and req.params.n_candidates:
            c = req.sampler.candidate_ids(host_out, off)
            if c is not None:
                cands = tuple(int(x) for x in c[:req.params.n_candidates])
        self._emit_token(i, req, int(tok), cands)

    def _emit_token(self, i: int, req: Request, tok: int, cands=None):
        """The shared per-token emission path (sampler picks and
        verified speculative runs alike): stop-sequence match,
        completion check, then deliver a TokenChunk to every consumer
        (with finish_reason set when this token finished the request)."""
        req.generated.append(tok)
        self.stats["emitted_tokens"] += 1
        if req.t_first is None:
            req.t_first = time.perf_counter()
            self._ttft_ms.append((req.t_first - req.t_submit) * 1e3)
        # stop-sequence matching at emission time, against the generated
        # tail — a sequence whose prefix landed in an earlier step
        # completes here for free (partial matches span step boundaries)
        for s in req.params.stop:
            if len(req.generated) >= len(s) \
                    and tuple(req.generated[-len(s):]) == s:
                req.finish_reason = "stop"
                break
        self._check_done(i)
        if self._consumers:
            chunk = TokenChunk(rid=req.rid, token=int(tok),
                               index=len(req.generated) - 1,
                               finish_reason=req.finish_reason,
                               candidate_ids=cands)
            for fn in list(self._consumers):
                fn(chunk)

    def _check_done(self, i: int):
        req = self.slots[i] if self.slots[i] else None
        if req is None:
            return
        if req.finish_reason == "stop":
            pass                      # a params.stop sequence matched
        elif req.generated and req.generated[-1] == self.eos_id:
            req.finish_reason = "eos"
        elif len(req.generated) >= req.max_new_tokens:
            req.finish_reason = "length"
        elif self.slot_pos[i] >= self.max_len - 1:
            # cache ceiling: the request is TRUNCATED short of its
            # max_new_tokens (submit warned about this combination)
            req.finish_reason = "max_len"
        else:
            return
        # stamp BEFORE done=True: unsynchronized readers (the facade's
        # pump mode polls req.done without the engine lock) must never
        # observe done with t_done still unset
        req.t_done = time.perf_counter()
        req.done = True
        self.stats["completed"] += 1
        self._release_slot(i)     # blocks back to the free list

    def run(self, max_iters: int = 1000):
        it = 0
        while (self.queue or any(s is not None for s in self.slots)) \
                and it < max_iters:
            self.step()
            it += 1
        return self.stats
