"""Tensor-parallel serving + the multi-replica router.

Two layers, mirroring the subsystem:

TP TRUNK (subprocess, 8 forced host devices — real pjit execution):
  - ``ServeEngine(tp=T)`` token streams are BIT-IDENTICAL to the
    unsharded engine for T in {2, 4}, including the compositions that
    exercise every sharded path: spec_k=4 (per-shard comparator verify)
    and host_stride=4 (device-resident multi-step loop).
  - sharded == reduced == softmax token streams under FORCED PREEMPTION
    (tight paged pool): sharding the trunk changes where work runs,
    never which tokens come out, even when scheduling differs.
  - the head's cross-shard traffic is O(rows * shards * k) (val, idx)
    pairs, never O(V) logit rows — asserted on the compiled HLO's
    collective result shapes.
  - ``Router(replicas=2, tp=2)`` == single unsharded ``LLM`` on the
    same trace (sharding x replication composes).
  - (4 forced host devices) ``Router(replicas=4)`` puts each tp=1
    replica's params and KV pools on its own device.

ROUTER (host-side, any device count — routing logic needs no mesh):
  - routing order: session affinity > prefix affinity > least-loaded
    (ties to lowest index, deterministic);
  - drain stops new work, clears the session map, in-flight completes;
    all-drained submission raises;
  - health() and the /v1/stats aggregate invariant
    ``engine.X == sum(replicas[i].engine.X)`` for every summed counter;
  - ``aggregate_engine_stats`` merge rules: counters sum, peaks max,
    ratios recomputed from summed terms, percentiles from pooled raw
    samples (None without samples).
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.configs import ARCHS, smoke_config
from repro.models import lm
from repro.serve.api import LLM
from repro.serve.params import SamplingParams
from repro.serve.router import (Router, aggregate_engine_stats,
                                aggregate_kv)

REPO = Path(__file__).resolve().parent.parent


def _run_sub(body: str, devices: int = 8) -> str:
    """Run ``body`` in a fresh interpreter with ``devices`` forced host
    devices (the flag must be set before jax initializes, hence the
    subprocess — same pattern as test_distributed)."""
    flag = f"--xla_force_host_platform_device_count={devices}"
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "{flag}"
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import ARCHS, smoke_config
        from repro.launch import mesh as mesh_mod, hlo_stats
        from repro.parallel import env
    """) + textwrap.dedent(body)
    env_ = dict(os.environ, PYTHONPATH=str(REPO / "src"), XLA_FLAGS=flag)
    out = subprocess.run([sys.executable, "-c", script], env=env_,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.fixture(scope="module")
def setup():
    import jax
    cfg = smoke_config(ARCHS["qwen3-0.6b"])
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(cfg, n=4, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=5 + 3 * i).astype(np.int32)
            for i in range(n)]


# ---------------------------------------------------------------------------
# Aggregation: the /v1/stats merge rules (pure functions, no engine)
# ---------------------------------------------------------------------------
def test_aggregate_engine_stats_merge_rules():
    a = {"emitted_tokens": 10, "decode_steps": 5, "drafted": 4,
         "accepted": 3, "host_syncs": 5, "peak_in_use": 7,
         "step_tokens": 40, "step_slots": 64, "live_peak": 3,
         "attn_kernel_steps": 5, "attn_blocks_read": 20,
         "attn_blocks_table": 80,
         "attn_approx": "exact", "attn_window": None}
    b = {"emitted_tokens": 6, "decode_steps": 3, "drafted": 0,
         "accepted": 0, "host_syncs": 3, "peak_in_use": 2,
         "step_tokens": 9, "step_slots": 16, "live_peak": 5,
         "attn_kernel_steps": 3, "attn_blocks_read": 7,
         "attn_blocks_table": 24}
    agg = aggregate_engine_stats([a, b], ttft_pools=[[10.0, 30.0], [20.0]])
    assert agg["emitted_tokens"] == 16          # counters sum
    assert agg["step_tokens"] == 49 and agg["step_slots"] == 80
    assert (agg["attn_kernel_steps"], agg["attn_blocks_read"],
            agg["attn_blocks_table"]) == (8, 27, 104)
    assert agg["peak_in_use"] == 7              # peaks max, never sum
    assert agg["live_peak"] == 5
    # ratios recomputed from summed terms — NOT averaged (replica b's
    # 0/0 must not dilute replica a's 3/4)
    assert agg["acceptance_rate"] == 3 / 4
    assert agg["tokens_per_dispatch"] == 16 / 8
    # percentiles from the pooled raw samples
    assert agg["ttft_ms_p50"] == 20.0
    assert agg["attn_approx"] == "exact"
    # no samples -> None, never a percentile-of-percentiles
    assert aggregate_engine_stats([a, b])["ttft_ms_p50"] is None
    assert aggregate_engine_stats([]) == {}


def test_aggregate_kv_merge_rules():
    u1 = {"layout": "paged", "block_size": 8, "num_blocks": 16,
          "blocks_in_use": 4, "peak_in_use": 9, "live_blocks": 3,
          "live_peak": 4}
    u2 = {"layout": "paged", "block_size": 8, "num_blocks": 16,
          "blocks_in_use": 2, "peak_in_use": 3, "live_blocks": 2,
          "live_peak": 6}
    agg = aggregate_kv([u1, u2])
    assert agg["num_blocks"] == 32              # disjoint pools sum
    assert agg["blocks_in_use"] == 6
    assert agg["live_blocks"] == 5
    assert agg["peak_in_use"] == 9              # worst single pool
    assert agg["live_peak"] == 6
    assert agg["block_size"] == 8


def test_llm_stats_payload_is_one_replica_fleet(setup):
    """A single LLM serves the same /v1/stats shape: aggregate == sole
    replica, so the invariant holds trivially."""
    cfg, params = setup
    llm = LLM(params, cfg, n_slots=2, max_len=32, eos_id=-1)
    llm.generate(_prompts(cfg, 2), SamplingParams(max_new_tokens=4))
    p = llm.stats_payload()
    assert len(p["replicas"]) == 1
    assert p["replicas"][0]["healthy"] is True
    assert p["engine"]["emitted_tokens"] == \
        p["replicas"][0]["engine"]["emitted_tokens"] == 8
    assert p["kv"] == p["replicas"][0]["kv"]


# ---------------------------------------------------------------------------
# Router: routing policy + lifecycle (host-side, no mesh needed)
# ---------------------------------------------------------------------------
def test_router_least_loaded_and_order(setup):
    cfg, params = setup
    router = Router(params, cfg, replicas=2, n_slots=2, max_len=32,
                    eos_id=-1)
    prompts = _prompts(cfg, 4)
    outs = router.generate(prompts, SamplingParams(max_new_tokens=4))
    # outputs in PROMPT order regardless of which replica served them
    assert [len(o.token_ids) for o in outs] == [4, 4, 4, 4]
    # generate submits all four before stepping, so routing sees the
    # queued work: least-loaded alternates 0,1,0,1 (ties to lowest idx)
    assert [r.served for r in router.replicas] == [2, 2]
    # the aggregate invariant, through the real payload
    p = router.stats_payload()
    for k in ("emitted_tokens", "decode_steps", "completed", "step_tokens",
              "step_slots", "attn_blocks_read", "attn_blocks_table"):
        assert p["engine"][k] == sum(r["engine"][k] for r in p["replicas"])
    assert p["engine"]["step_tokens"] > 0
    assert p["engine"]["attn_blocks_table"] > 0
    assert p["engine"]["live_peak"] == \
        max(r["engine"]["live_peak"] for r in p["replicas"])
    assert p["engine"]["emitted_tokens"] == 16
    assert p["kv"]["num_blocks"] == \
        sum(r["kv"]["num_blocks"] for r in p["replicas"])


def test_router_session_affinity(setup):
    cfg, params = setup
    router = Router(params, cfg, replicas=3, n_slots=2, max_len=32,
                    eos_id=-1)
    prompts = _prompts(cfg, 6)
    idxs = [router.route(p, session="conv-1") for p in prompts]
    assert len(set(idxs)) == 1                 # sticky
    # a different session is NOT stuck to the same replica: the first
    # one's load pushes least-loaded elsewhere
    other = router.route(prompts[0], session="conv-2")
    assert other == idxs[0]                    # load() is 0: ties to 0...
    # ...until real work pins load; route() itself only bumps `served`,
    # so force the tie-break by queueing work on replica 0
    router.replicas[idxs[0]].llm.submit(prompts[0],
                                        SamplingParams(max_new_tokens=2))
    assert router.route(prompts[1], session="conv-3") != idxs[0]


def test_router_prefix_affinity(setup):
    """A replica holding the prompt's prefix in its trie wins routing
    even when another replica is less loaded."""
    cfg, params = setup
    router = Router(params, cfg, replicas=2, n_slots=2, max_len=64,
                    eos_id=-1, kv_layout="paged", block_size=8,
                    chunk_size=8)
    shared = np.arange(2, 26, dtype=np.int32) % cfg.vocab_size   # 3 blocks
    # serve the shared prompt once — router picks replica 0 (idle tie),
    # which publishes the prefix into ITS trie on completion
    router.generate([shared], SamplingParams(max_new_tokens=2))
    assert router.replicas[0].served == 1
    assert router.replicas[0].prefix_hit(shared) > 0
    assert router.replicas[1].prefix_hit(shared) == 0
    # same prefix, longer prompt: replica 1 is equally loaded and would
    # win nothing — prefix affinity must route back to replica 0
    follow = np.concatenate([shared, np.array([7, 9], np.int32)])
    assert router.route(follow) == 0
    # an unrelated prompt falls through to least-loaded
    rng = np.random.default_rng(0)
    cold = rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
    assert router.route(cold) in (0, 1)


def test_router_drain_and_health(setup):
    cfg, params = setup
    router = Router(params, cfg, replicas=2, n_slots=2, max_len=32,
                    eos_id=-1)
    prompts = _prompts(cfg, 2)
    router.route(prompts[0], session="s0")
    pinned = router._sessions["s0"]
    router.drain(pinned)
    # drained replica: no new routes, session map entry cleared
    assert "s0" not in router._sessions
    for p in prompts:
        assert router.route(p) == 1 - pinned
        assert router.route(p, session="s0") == 1 - pinned
    h = router.health()
    assert h["ok"] is True                     # one replica still up
    assert h["replicas"][pinned]["draining"] is True
    # draining everything makes submission fail loudly
    router.drain(1 - pinned)
    assert router.health()["ok"] is False
    with pytest.raises(RuntimeError, match="no healthy replica"):
        router.route(prompts[0])
    router.undrain(pinned)
    assert router.route(prompts[0]) == pinned
    # in-flight work on a draining replica still completes
    router.undrain(1 - pinned)
    outs = router.generate(prompts, SamplingParams(max_new_tokens=3))
    assert all(len(o.token_ids) == 3 for o in outs)


def test_router_generate_matches_single_llm(setup):
    """Replication is invisible in the tokens: the 2-replica fleet and
    one engine emit identical greedy streams (sampled rows pin explicit
    seeds — facade rids differ per replica, so the rid-derived default
    stream would legitimately differ)."""
    cfg, params = setup
    prompts = _prompts(cfg, 4)
    plist = [SamplingParams(max_new_tokens=6, seed=100 + i,
                            top_k=3 if i == 1 else 1,
                            temperature=0.8 if i == 1 else 1.0)
             for i in range(4)]
    single = LLM(params, cfg, n_slots=2, max_len=48, eos_id=-1)
    want = [list(o.token_ids) for o in
            single.generate([p.copy() for p in prompts], plist)]
    router = Router(params, cfg, replicas=2, n_slots=2, max_len=48,
                    eos_id=-1)
    got = [list(o.token_ids) for o in
           router.generate([p.copy() for p in prompts], plist)]
    assert got == want
    assert all(r.served > 0 for r in router.replicas)   # really split


def test_router_stream_and_pump(setup):
    cfg, params = setup
    router = Router(params, cfg, replicas=2, n_slots=2, max_len=32,
                    eos_id=-1)
    router.start_pump()
    try:
        toks = [c.token for c in
                router.stream(_prompts(cfg, 1)[0],
                              SamplingParams(max_new_tokens=5))]
        assert len(toks) == 5
        assert router.health()["ok"] is True
    finally:
        router.stop_pump()


def test_router_rejects_bad_replicas(setup):
    cfg, params = setup
    with pytest.raises(ValueError, match="replicas=0"):
        Router(params, cfg, replicas=0)


def test_sampling_params_spec_k_accepts_sharded_head():
    SamplingParams(spec_k=4, head_mode="sharded")        # must not raise
    with pytest.raises(ValueError, match="softmax"):
        SamplingParams(spec_k=4, head_mode="softmax")


# ---------------------------------------------------------------------------
# TP trunk: subprocess with 8 forced host devices (real pjit execution)
# ---------------------------------------------------------------------------
def test_tp_engine_identity_8dev():
    """tp in {2, 4} == unsharded, including the stacked compositions:
    mixed samplers, spec_k=4 comparator verify, host_stride=4 device
    loop.  The acceptance bar for the sharded trunk."""
    out = _run_sub("""
        from repro.models import lm
        from repro.serve.engine import Request, ServeEngine
        from repro.serve.params import SamplingParams
        cfg = smoke_config(ARCHS["qwen3-0.6b"])
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        prompts = [np.arange(2, 2 + n, dtype=np.int32) % cfg.vocab_size
                   for n in (5, 9, 13, 4)]

        def run(tp=None, spec_k=0, host_stride=None):
            eng = ServeEngine(params, cfg, n_slots=3, max_len=64,
                              head_mode="reduced", tp=tp, chunk_size=8,
                              host_stride=host_stride, seed=7)
            reqs = []
            for r, p in enumerate(prompts):
                mixed = spec_k == 0 and r == 1      # spec_k needs greedy
                sp = SamplingParams(max_new_tokens=10, spec_k=spec_k,
                                    top_k=4 if mixed else 1,
                                    temperature=0.8 if mixed else 1.0,
                                    seed=r)
                reqs.append(Request(rid=r, prompt=p.copy(), params=sp))
                eng.submit(reqs[-1])
            eng.run(max_iters=200)
            if tp:
                assert eng.tp == tp and eng.head_mode == "sharded"
            return [tuple(r.generated) for r in reqs], eng

        base, _ = run(tp=None)
        for tp in (2, 4):
            got, _ = run(tp=tp)
            assert got == base, (tp, got, base)
        sb, _ = run(tp=None, spec_k=4)
        st, eng = run(tp=2, spec_k=4)
        assert st == sb, (st, sb)
        assert eng.stats["accepted"] > 0                   # verify ran
        hb, _ = run(tp=None, host_stride=4)
        ht, _ = run(tp=2, host_stride=4)
        assert ht == hb, (ht, hb)
        print("TP IDENTITY OK")
    """)
    assert "TP IDENTITY OK" in out


def test_tp_sharded_head_matches_softmax_under_preemption_8dev():
    """sharded == reduced == softmax streams on a tight paged pool that
    FORCES preemption: the comparator head stays exact when re-prefill
    reshuffles scheduling, and the softmax baseline agrees."""
    out = _run_sub("""
        from repro.models import lm
        from repro.serve.engine import Request, ServeEngine
        from repro.serve.params import SamplingParams
        cfg = smoke_config(ARCHS["qwen3-0.6b"])
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
                   for _ in range(3)]

        def run(head_mode, tp=None, tight=False):
            kw = dict(kv_layout="paged", block_size=8)
            if tight:
                kw["num_blocks"] = 4            # forces preempt+reprefill
            eng = ServeEngine(params, cfg, n_slots=2, max_len=64,
                              head_mode=head_mode, tp=tp, seed=7, **kw)
            reqs = [Request(i, p.copy(),
                            params=SamplingParams(max_new_tokens=12))
                    for i, p in enumerate(prompts)]
            for r in reqs:
                eng.submit(r)
            eng.run(max_iters=300)
            return [tuple(r.generated) for r in reqs], eng

        want, _ = run("softmax")
        red, _ = run("reduced")
        assert red == want, (red, want)
        ample, _ = run("reduced", tp=2)
        assert ample == want
        tight, eng = run("reduced", tp=2, tight=True)
        assert tight == want, (tight, want)
        assert eng.stats["preemptions"] >= 1    # scheduling DID differ
        print("PREEMPT IDENTITY OK")
    """)
    assert "PREEMPT IDENTITY OK" in out


def test_tp_router_matches_unsharded_llm_8dev():
    """The full stack: Router(replicas=2, tp=2) over disjoint device
    slices == one unsharded LLM, token for token."""
    out = _run_sub("""
        from repro.models import lm
        from repro.serve.api import LLM
        from repro.serve.params import SamplingParams
        from repro.serve.router import Router
        cfg = smoke_config(ARCHS["qwen3-0.6b"])
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, cfg.vocab_size, 5 + 3 * i)
                     .astype(np.int32) for i in range(4)]
        plist = [SamplingParams(max_new_tokens=6, seed=100 + i)
                 for i in range(4)]
        single = LLM(params, cfg, n_slots=2, max_len=48, eos_id=-1)
        want = [list(o.token_ids) for o in
                single.generate([p.copy() for p in prompts], plist)]
        router = Router(params, cfg, replicas=2, tp=2, n_slots=2,
                        max_len=48, eos_id=-1)
        # disjoint slices: replica r owns devices [2r, 2r+2)
        for r in router.replicas:
            assert r.llm.engine.tp == 2
        d0 = set(router.replicas[0].llm.engine.mesh.devices.flat)
        d1 = set(router.replicas[1].llm.engine.mesh.devices.flat)
        assert not (d0 & d1)
        got = [list(o.token_ids) for o in
               router.generate([p.copy() for p in prompts], plist)]
        assert got == want, (got, want)
        assert all(r.served > 0 for r in router.replicas)
        p = router.stats_payload()
        assert p["engine"]["emitted_tokens"] == 24 == \\
            sum(r["engine"]["emitted_tokens"] for r in p["replicas"])
        print("ROUTER TP OK")
    """)
    assert "ROUTER TP OK" in out


def test_router_places_replicas_on_distinct_devices_4dev():
    """Replica r owns device r (modulo the devices present): four tp=1
    replicas put their params AND KV pools on four distinct devices,
    /v1/stats names them, a fifth wraps onto device 0, and the fleet
    still emits the single LLM's tokens."""
    out = _run_sub("""
        from repro.models import lm
        from repro.serve.api import LLM
        from repro.serve.params import SamplingParams
        from repro.serve.router import Router
        cfg = smoke_config(ARCHS["qwen3-0.6b"])
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, cfg.vocab_size, 5 + 3 * i)
                     .astype(np.int32) for i in range(4)]
        sp = SamplingParams(max_new_tokens=5)
        single = LLM(params, cfg, n_slots=2, max_len=32, eos_id=-1)
        want = [list(o.token_ids) for o in single.generate(prompts, sp)]

        router = Router(params, cfg, replicas=4, n_slots=2, max_len=32,
                        eos_id=-1)
        devs = jax.devices()
        for r in router.replicas:
            eng = r.llm.engine
            assert eng.devices == [devs[r.idx]], (r.idx, eng.devices)
            for pool in eng.store.pools:
                assert pool.devices() == {devs[r.idx]}, r.idx
        names = [rep["devices"] for rep in router.stats_payload()["replicas"]]
        assert names == [[f"cpu:{d.id}"] for d in devs], names
        got = [list(o.token_ids) for o in router.generate(prompts, sp)]
        assert got == want, (got, want)
        assert all(r.served == 1 for r in router.replicas)

        wrap = Router(params, cfg, replicas=5, n_slots=2, max_len=32)
        assert wrap.replicas[4].llm.engine.devices == [devs[0]]
        print("ROUTER PLACEMENT OK")
    """, devices=4)
    assert "ROUTER PLACEMENT OK" in out


def test_sharded_head_collectives_are_o_k_not_o_v_8dev():
    """HLO-level proof of the paper's scaling claim at the head: compile
    the vocab-sharded k-winner bus and sum the collective result shapes
    — cross-shard traffic must be O(rows * shards * k) (val, idx) pairs,
    a small fraction of the O(rows * V) a logit all-gather would move."""
    out = _run_sub("""
        from repro.core import reduced_softmax
        B, D, V, K = 8, 64, 4096, 4
        mesh = mesh_mod.make_host_mesh(model=8)
        h = jnp.zeros((B, D), jnp.float32)
        w = jnp.zeros((D, V), jnp.float32)
        with env.use_mesh(mesh):
            fn = jax.jit(lambda hh, ww: reduced_softmax.sharded_reduced_topk(
                hh, ww, K, env.current_mesh(), data_axes=()))
            txt = fn.lower(h, w).compile().as_text()
        coll = hlo_stats.collective_bytes(txt)
        total = sum(coll.values())
        logit_bytes = B * V * 4                 # one f32 logit row sweep
        print("HEAD COLL", sorted(coll.items()), "total", total,
              "vs O(V)", logit_bytes)
        assert total > 0, "no collectives found - not actually sharded"
        assert total < logit_bytes / 4, (total, logit_bytes)
        print("O_K OK")
    """)
    assert "O_K OK" in out
