"""Multi-replica router: N serving engines behind one admission surface.

The scale-out layer above the (optionally tensor-parallel) engine: a
``Router`` owns N ``LLM`` replicas — each a full continuous-batching
engine with its own KV pool, prefix trie and background pump — and
routes every incoming request to exactly one of them:

  1. SESSION AFFINITY: a request tagged with a ``session`` id goes to
     the replica that served that session before (the sticky map is
     established on first sight and cleared when the replica drains),
     so a conversation keeps hitting the KV prefixes it already built.
  2. PREFIX AFFINITY: otherwise, each candidate replica's ``PrefixTrie``
     is probed with the prompt (``store.match_prefix`` — the same
     longest-whole-block-run lookup admission uses) and the replica
     with the longest cached run wins: the request adopts those pool
     blocks at admission and prefills only its suffix, so shared
     system prompts stay hot on ONE replica instead of being
     re-prefilled on all of them.
  3. LEAST LOADED: no cached prefix anywhere -> the replica with the
     fewest in-flight tokens of work (queued + active), ties to the
     lowest index (deterministic).

Replicas are health-checked (pump thread alive, no engine error) and
DRAINABLE: ``drain(i)`` stops routing new work to replica ``i`` while
its in-flight requests run to completion — the rolling-restart
primitive.  Draining/unhealthy replicas are skipped by the router; if
every replica is unhealthy, submission raises.

Placement: replica r gets device slice r — devices ``[r*T, (r+1)*T)``
for ``tp=T`` (a ``(1, T)`` mesh), device ``r`` for a one-device replica
— taken modulo the devices present.  Slices are disjoint while
``replicas * T`` devices exist and wrap around onto shared devices
beyond that; correct either way, the slices only matter for real
parallel speedup.  Each replica's ``/v1/stats`` entry names its
devices.

Stats aggregate across replicas with explicit merge rules (the shape
GET /v1/stats serves — see ``aggregate_engine_stats``): counters SUM,
peaks take the MAX over replicas, ratios are recomputed from the summed
numerators/denominators, and latency percentiles are recomputed from
the POOLED per-request samples (exact when the raw samples are
available, as they are here; any consumer merging from snapshots alone
must treat merged percentiles as approximate).
"""
from __future__ import annotations

import threading
import time
from typing import Iterator, List, Optional, Sequence

import numpy as np

from repro.serve.api import LLM, PromptLike, _is_single_prompt
from repro.serve.outputs import RequestOutput, TokenChunk
from repro.serve.params import SamplingParams

# engine counters that SUM across replicas (each replica's counter is an
# independent event count); gauges queue_depth/active_slots also sum —
# "how much work is in the fleet".
_SUM_KEYS = (
    "prefills", "prefill_chunks", "decode_steps", "iterations",
    "fused_rows", "completed", "deferred", "preemptions", "cancelled",
    "drafted", "accepted", "host_syncs", "emitted_tokens",
    "prefix_hits", "prefix_hit_tokens", "prefill_tokens",
    "step_tokens", "step_slots", "step_host_ns",
    "attn_kernel_steps", "attn_blocks_read", "attn_blocks_table",
    "queue_depth", "active_slots", "cow_copies", "shared_blocks",
)
# peaks take the max over replicas: the worst single-pool pressure seen
# anywhere, NOT a fleet total (pools are disjoint, so a sum would mix
# high-watermarks that never coexisted).
_MAX_KEYS = ("peak_in_use", "live_peak")


def aggregate_engine_stats(snaps: Sequence[dict],
                           ttft_pools: Optional[Sequence[Sequence[float]]]
                           = None) -> dict:
    """Merge per-replica ``engine.snapshot()`` dicts into one aggregate.

    Merge rules (the contract /v1/stats documents): counters and work
    gauges sum; peaks max; ``acceptance_rate`` and
    ``tokens_per_dispatch`` are recomputed from the summed
    numerators/denominators (never averaged — an idle replica must not
    dilute a busy one); TTFT percentiles are recomputed from the pooled
    raw samples when ``ttft_pools`` is given (exact), else dropped to
    None (percentiles of percentiles would be wrong).  Engine-wide mode
    fields (attn_approx/attn_window) come from the first replica —
    replicas are homogeneous by construction.
    """
    if not snaps:
        return {}
    agg = {k: sum(int(s.get(k, 0)) for s in snaps) for k in _SUM_KEYS}
    for k in _MAX_KEYS:
        agg[k] = max(int(s.get(k, 0)) for s in snaps)
    agg["acceptance_rate"] = (agg["accepted"] / agg["drafted"]
                              if agg["drafted"] else 0.0)
    agg["tokens_per_dispatch"] = (agg["emitted_tokens"]
                                  / max(agg["host_syncs"], 1))
    agg["attn_approx"] = snaps[0].get("attn_approx")
    agg["attn_window"] = snaps[0].get("attn_window")
    samples: List[float] = []
    if ttft_pools is not None:
        for pool in ttft_pools:
            samples.extend(pool)
    if samples:
        t = np.asarray(samples)
        agg["ttft_ms_p50"] = float(np.percentile(t, 50))
        agg["ttft_ms_p99"] = float(np.percentile(t, 99))
    else:
        agg["ttft_ms_p50"] = agg["ttft_ms_p99"] = None
    return agg


def aggregate_kv(usages: Sequence[dict]) -> dict:
    """Merge per-replica ``store.usage()`` dicts: block counts sum
    (pools are disjoint), peaks (``peak_in_use``, ``live_peak``) max,
    layout/block_size come from the first replica (homogeneous)."""
    if not usages:
        return {}
    out = {"layout": usages[0]["layout"],
           "block_size": usages[0]["block_size"]}
    for k in ("num_blocks", "blocks_free", "blocks_in_use", "paged_leaves",
              "dense_leaves", "shared_blocks", "prefix_blocks",
              "blocks_reclaimable", "cow_copies", "prefix_evictions",
              "live_blocks"):
        out[k] = sum(int(u.get(k, 0)) for u in usages)
    for k in ("peak_in_use", "live_peak"):
        out[k] = max(int(u.get(k, 0)) for u in usages)
    return out


class Replica:
    """One engine replica plus its router-side state."""

    def __init__(self, idx: int, llm: LLM):
        self.idx = idx
        self.llm = llm
        self.draining = False
        self.served = 0               # requests routed here (router stat)

    @property
    def healthy(self) -> bool:
        """A replica is healthy while its engine can make progress: no
        pump error (a pump that was never started still steps inline,
        so 'not pumping' is not unhealthy)."""
        return self.llm._pump_error is None

    def load(self) -> int:
        """In-flight work: queued + active requests.  Read without the
        engine lock — a stale-by-one count only perturbs tie-breaks."""
        eng = self.llm.engine
        return len(eng.queue) + sum(s is not None for s in eng.slots)

    def prefix_hit(self, prompt) -> int:
        """Longest cached prefix (tokens) this replica's trie holds for
        ``prompt`` — the affinity signal.  Probing bumps LRU stamps,
        which is harmless (at worst it keeps a contended run warm)."""
        with self.llm._lock:
            _, hit = self.llm.engine.store.match_prefix(prompt)
        return hit


class Router:
    """N ``LLM`` replicas behind one submit/generate/stream surface.

    Constructor mirrors ``LLM``: ``Router(params, cfg, replicas=N,
    tp=T, **engine_kwargs)`` builds N identical engines (sharing the
    immutable param arrays; each owns its KV store).  The router is a
    drop-in for ``LLM`` in ``serve/server.py`` — it implements the same
    ``generate``/``stream``/``start_pump``/``health``/``stats_payload``
    surface the handler consumes.
    """

    def __init__(self, params, cfg, *, replicas: int = 2,
                 tp: Optional[int] = None, **engine_kwargs):
        if replicas < 1:
            raise ValueError(f"replicas={replicas}: must be >= 1")
        import jax

        from repro import compat
        devs = jax.devices()
        width = tp if tp is not None and tp > 1 else 1
        if len(devs) < width:
            raise ValueError(
                f"tp={tp} needs {tp} devices; only {len(devs)} "
                "visible (on a CPU host set XLA_FLAGS="
                f"--xla_force_host_platform_device_count="
                f"{replicas * tp} before jax initializes)")
        self.replicas = []
        for r in range(replicas):
            # replica r owns device slice r, [r*width, (r+1)*width),
            # taken modulo the devices present: disjoint slices while
            # replicas * width devices exist, shared ones beyond that.
            sl = [devs[(r * width + j) % len(devs)] for j in range(width)]
            kw = dict(engine_kwargs)
            if tp is not None:
                kw["tp"] = tp
            if width > 1:
                kw["mesh"] = compat.make_mesh((1, width), ("data", "model"),
                                              devices=sl)
            else:
                kw["device"] = sl[0]
            self.replicas.append(Replica(r, LLM(params, cfg, **kw)))
        self.cfg = self.replicas[0].llm.cfg
        self._route_lock = threading.Lock()
        self._sessions: dict = {}          # session id -> replica idx

    @classmethod
    def from_arch(cls, arch: str, *, smoke: bool = False, seed: int = 0,
                  **kwargs) -> "Router":
        import jax

        from repro.configs import get_config, smoke_config
        from repro.models import lm

        cfg = get_config(arch)
        if smoke:
            cfg = smoke_config(cfg)
        params = lm.init_params(cfg, jax.random.PRNGKey(seed))
        return cls(params, cfg, seed=seed, **kwargs)

    # -- routing -------------------------------------------------------------
    def _candidates(self) -> List[Replica]:
        up = [r for r in self.replicas if r.healthy and not r.draining]
        if not up:
            raise RuntimeError(
                "no healthy replica accepting work: "
                + ", ".join(f"replica {r.idx}: "
                            + ("draining" if r.draining else "pump died")
                            for r in self.replicas))
        return up

    def route(self, prompt: PromptLike,
              session: Optional[str] = None) -> int:
        """Pick the replica index for this request (see module doc:
        session -> prefix -> least-loaded)."""
        with self._route_lock:
            cands = self._candidates()
            ok = {r.idx for r in cands}
            if session is not None and self._sessions.get(session) in ok:
                return self._sessions[session]
            prompt = np.asarray(prompt, np.int32)
            hits = [(r.prefix_hit(prompt), r) for r in cands]
            best_hit = max(h for h, _ in hits)
            if best_hit > 0:
                pool = [r for h, r in hits if h == best_hit]
            else:
                pool = cands
            pick = min(pool, key=lambda r: (r.load(), r.idx))
            if session is not None:
                self._sessions[session] = pick.idx
            pick.served += 1
            return pick.idx

    # -- the LLM surface -----------------------------------------------------
    def submit(self, prompt: PromptLike,
               params: Optional[SamplingParams] = None,
               session: Optional[str] = None):
        """Route + submit; returns ``(Request, replica_idx)``."""
        idx = self.route(prompt, session)
        return self.replicas[idx].llm.submit(prompt, params), idx

    def generate(self, prompts,
                 params=None, sessions=None) -> List[RequestOutput]:
        """Serve prompt(s) across the fleet; outputs in prompt order."""
        if not isinstance(prompts, np.ndarray):
            prompts = list(prompts)
        if _is_single_prompt(prompts):
            prompts = [prompts]
        prompts = list(prompts)
        if params is None or isinstance(params, SamplingParams):
            plist = [params] * len(prompts)
        else:
            plist = list(params)
            if len(plist) != len(prompts):
                raise ValueError(f"{len(plist)} SamplingParams for "
                                 f"{len(prompts)} prompts")
        if sessions is None:
            slist = [None] * len(prompts)
        else:
            slist = list(sessions)
        reqs = [self.submit(p, sp, session=s)[0]
                for p, sp, s in zip(prompts, plist, slist)]
        self._drive_until(lambda: all(r.done for r in reqs))
        return [RequestOutput.from_request(r) for r in reqs]

    def stream(self, prompt: PromptLike,
               params: Optional[SamplingParams] = None,
               session: Optional[str] = None) -> Iterator[TokenChunk]:
        idx = self.route(prompt, session)
        return self.replicas[idx].llm.stream(prompt, params)

    def _drive_until(self, pred) -> None:
        """Advance every replica with work until ``pred()`` — inline
        round-robin steps when no pump is running (each replica steps
        under its own lock), otherwise just wait on the pumps."""
        while not pred():
            for r in self.replicas:
                if r.llm._pump_error is not None:
                    raise RuntimeError(
                        f"replica {r.idx} engine pump died"
                    ) from r.llm._pump_error
            if any(r.llm._pumping for r in self.replicas):
                time.sleep(0.001)
                continue
            progressed = False
            for r in self.replicas:
                with r.llm._lock:
                    if r.llm.engine.has_work:
                        r.llm.engine.step()
                        progressed = True
            if not progressed and not pred():
                raise RuntimeError(
                    "router idle with unfinished requests — a request "
                    "was lost (bug) or never submitted")

    # -- lifecycle -----------------------------------------------------------
    def start_pump(self, idle_wait: float = 0.005) -> None:
        for r in self.replicas:
            r.llm.start_pump(idle_wait)

    def stop_pump(self) -> None:
        """Stop every replica's pump, then re-raise the first pump error
        (``LLM.stop_pump``) — one dead replica never leaves the others
        running."""
        first = None
        for r in self.replicas:
            try:
                r.llm.stop_pump()
            except RuntimeError as e:
                first = first or e
        if first is not None:
            raise first

    def drain(self, idx: int, wait: bool = False,
              timeout: float = 60.0) -> None:
        """Stop routing new work to replica ``idx``; in-flight requests
        run to completion.  ``wait=True`` blocks until the replica is
        idle (its pump must be running, or callers must keep driving)."""
        rep = self.replicas[idx]
        rep.draining = True
        with self._route_lock:
            self._sessions = {s: i for s, i in self._sessions.items()
                              if i != idx}
        if wait:
            deadline = time.monotonic() + timeout
            while rep.llm.engine.has_work:
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"replica {idx} still busy after {timeout}s")
                if not rep.llm._pumping:
                    with rep.llm._lock:
                        if rep.llm.engine.has_work:
                            rep.llm.engine.step()
                else:
                    time.sleep(0.005)

    def undrain(self, idx: int) -> None:
        self.replicas[idx].draining = False

    # -- introspection (the server surface) ----------------------------------
    def health(self) -> dict:
        reps = [{"replica": r.idx, "ok": r.healthy,
                 "draining": r.draining,
                 "pumping": r.llm._pumping,
                 "has_work": r.llm.engine.has_work,
                 **({} if r.llm._pump_error is None else
                    {"error": f"engine pump died: {r.llm._pump_error}"})}
                for r in self.replicas]
        # the fleet is OK while at least one replica can take new work
        ok = any(r["ok"] and not r["draining"] for r in reps)
        return {"ok": ok, "replicas": reps}

    def stats_payload(self) -> dict:
        """The /v1/stats shape: aggregate engine+kv (merge rules in
        ``aggregate_engine_stats``) plus the per-replica breakdown."""
        snaps, usages, pools, reps = [], [], [], []
        for r in self.replicas:
            with r.llm._lock:
                snap = r.llm.engine.snapshot()
                usage = r.llm.engine.store.usage()
                pool = list(r.llm.engine._ttft_ms)
            snaps.append(snap)
            usages.append(usage)
            pools.append(pool)
            reps.append({"replica": r.idx, "engine": snap, "kv": usage,
                         "healthy": r.healthy, "draining": r.draining,
                         "routed": r.served,
                         "devices": r.llm.device_names()})
        return {"engine": aggregate_engine_stats(snaps, pools),
                "kv": aggregate_kv(usages),
                "replicas": reps}

    @property
    def stats(self) -> dict:
        """Aggregate engine counters (the LLM-compatible property)."""
        return self.stats_payload()["engine"]

    def kv_usage(self) -> dict:
        return aggregate_kv([r.llm.engine.store.usage()
                             for r in self.replicas])
