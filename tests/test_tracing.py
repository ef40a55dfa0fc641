"""The serving path's trace names and step counters.

  - HOST SPANS: under ``jax.profiler`` one ``ServeEngine.step`` writes a
    ``serve.step`` span (args: the dispatched shape) whose children are
    ``serve.admit``, ``serve.plan``, ``serve.operands``,
    ``serve.dispatch``, ``serve.sync`` and ``serve.emit``;
    ``ServeEngine.submit`` writes ``serve.submit``.
  - DEVICE SCOPES: the lowered step program carries the scope names
    ``qkv_proj``, ``kv_write``, ``attention``, ``out_proj``, ``mlp``,
    ``head`` and ``verify`` in its op metadata, and the scopes change
    nothing else: the program and the tokens are those of a trace with
    every scope taken out.
  - COUNTERS: ``step_tokens`` / ``step_slots`` against a hand count;
    ``attn_kernel_steps`` / ``attn_blocks_read`` / ``attn_blocks_table``
    against a hand count under the reference and under the kernel;
    ``live_blocks`` against a brute-force count of the slot tables
    through alloc, adopt, release, preempt, rewind and evict;
    ``reset_live_peak``.
"""
import contextlib
import glob
import re

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, smoke_config
from repro.models import lm
from repro.serve import engine as engine_mod
from repro.serve.engine import Request, ServeEngine
from repro.serve.paged_kv import PagedKVStore
from repro.serve.params import SamplingParams

SCOPES = ("qkv_proj", "kv_write", "attention", "out_proj", "mlp", "head",
          "verify")
CHILDREN = {"serve.admit", "serve.plan", "serve.operands", "serve.dispatch",
            "serve.sync", "serve.emit"}


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_config(ARCHS["qwen3-0.6b"])
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    yield cfg, params
    jax.clear_caches()


def _prompt(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(2, cfg.vocab_size, n).astype(np.int32)


def _engine(cfg, params, **kw):
    kw = {"n_slots": 4, "max_len": 64, "eos_id": -1, "block_size": 4,
          "chunk_size": 4, **kw}
    return ServeEngine(params, cfg, **kw)


def _scopes_in(text) -> set:
    """Scope names among the location paths of lowered program text."""
    paths = re.findall(r'loc\("([^"]*)"', text)
    return {name for name in SCOPES
            if any(f"/{name}/" in f"/{p}" for p in paths)}


def _live_brute(store) -> int:
    return len({b for table in store.slot_blocks for b in table})


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------
def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    assert path, "the profiler wrote no trace"
    out = []
    for plane in ProfileData.from_file(path[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)) for e in line.events
                    if e.name.startswith("serve.")]
    return out


def test_step_writes_host_spans_with_six_children(setup, tmp_path):
    cfg, params = setup
    eng = _engine(cfg, params)
    eng.submit(Request(0, _prompt(cfg, 3, 1),
                       params=SamplingParams(max_new_tokens=4)))
    eng.step()                                   # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        eng.submit(Request(1, _prompt(cfg, 10, 2),
                           params=SamplingParams(max_new_tokens=4)))
        eng.step()
    events = _host_events(tmp_path)
    names = [e[0] for e in events]
    assert names.count("serve.submit") == 1
    (_, s0, e0, args), = [e for e in events if e[0] == "serve.step"]
    # one decode row + one 4-token chunk row, padded to 2 rows x T 4
    assert args == {"rows": 2, "padded_rows": 2, "T": 4, "chunk_rows": 1,
                    "tokens": 5}
    kids = [e for e in events if e[0] in CHILDREN]
    assert {e[0] for e in kids} == CHILDREN
    assert all(s0 <= s and e <= e0 for _, s, e, _ in kids)


# ---------------------------------------------------------------------------
# device scopes
# ---------------------------------------------------------------------------
def _decoding_engine(cfg, params):
    """An engine whose two slots are past prefill, and those slots."""
    eng = _engine(cfg, params)
    for rid in range(2):
        eng.submit(Request(rid, _prompt(cfg, 5, 10 + rid),
                           params=SamplingParams(max_new_tokens=20,
                                                 spec_k=2)))
    while any(eng._prefilling(i) or eng.slots[i] is None for i in (0, 1)):
        eng.step()
    return eng, [0, 1]


def test_every_scope_names_ops_of_the_lowered_step(setup):
    cfg, params = setup
    eng, rows = _decoding_engine(cfg, params)
    drafts = {rows[0]: [3, 4], rows[1]: []}      # a verify and a head group
    fn, args, *_ = eng._step_operands(rows, drafts, {})
    assert _scopes_in(fn.lower(*args).as_text(debug_info=True)) \
        == set(SCOPES)


def test_multistep_head_is_scoped(setup):
    cfg, params = setup
    eng = _engine(cfg, params, host_stride=4)
    eng.submit(Request(0, _prompt(cfg, 5, 3),
                       params=SamplingParams(max_new_tokens=12)))
    while eng.slots[0] is None or eng._prefilling(0):
        eng.step()
    fn, args, _ = eng._multi_operands([0], {0: 4})
    assert _scopes_in(fn.lower(*args).as_text(debug_info=True)) \
        == set(SCOPES) - {"verify"}


@contextlib.contextmanager
def _no_scopes(monkeypatch):
    """Trace with every ``jax.named_scope`` taken out (fresh compiles)."""
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    engine_mod._jitted_step.cache_clear()
    engine_mod._jitted_multistep.cache_clear()
    try:
        yield
    finally:
        monkeypatch.undo()
        engine_mod._jitted_step.cache_clear()
        engine_mod._jitted_multistep.cache_clear()


def test_scopes_change_neither_program_nor_tokens(setup, monkeypatch):
    cfg, params = setup

    def serve(**kw):
        eng = _engine(cfg, params, **kw)
        spec = 0 if "host_stride" in kw else 2
        reqs = [Request(i, _prompt(cfg, 6 + 5 * i, 20 + i),
                        params=SamplingParams(max_new_tokens=10,
                                              spec_k=spec if i == 1 else 0))
                for i in range(3)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        return [r.generated for r in reqs]

    eng, rows = _decoding_engine(cfg, params)
    drafts = {rows[0]: [3, 4], rows[1]: []}
    fn, args, *_ = eng._step_operands(rows, drafts, {})
    scoped_text = fn.lower(*args).as_text()
    scoped = serve(), serve(host_stride=4)
    with _no_scopes(monkeypatch):
        fn, args, *_ = eng._step_operands(rows, drafts, {})
        assert not _scopes_in(fn.lower(*args).as_text(debug_info=True))
        plain_text = fn.lower(*args).as_text()
        plain = serve(), serve(host_stride=4)
    assert scoped_text == plain_text
    assert scoped == plain


# ---------------------------------------------------------------------------
# step counters
# ---------------------------------------------------------------------------
def test_step_tokens_and_slots_hand_count(setup):
    cfg, params = setup
    eng = _engine(cfg, params)
    eng.submit(Request(0, _prompt(cfg, 3, 4),
                       params=SamplingParams(max_new_tokens=8)))
    eng.step()               # one final 3-token chunk: 1 row x T 4
    assert (eng.stats["step_tokens"], eng.stats["step_slots"]) == (3, 4)
    for rid, n in ((1, 10), (2, 6)):
        eng.submit(Request(rid, _prompt(cfg, n, 5 + rid),
                           params=SamplingParams(max_new_tokens=8)))
    eng.step()
    # row 0 decodes 1 token; rows 1 and 2 carry 4-token chunks:
    # 3 real rows padded to 4, T 4
    assert (eng.stats["step_tokens"], eng.stats["step_slots"]) \
        == (3 + 1 + 4 + 4, 4 + 4 * 4)
    assert eng.snapshot()["step_tokens"] == 12
    assert eng.stats["step_host_ns"] > 0


def test_step_tokens_on_the_multistep_path_count_emissions(setup):
    cfg, params = setup
    eng = _engine(cfg, params, host_stride=4)
    for rid in range(3):
        eng.submit(Request(rid, _prompt(cfg, 4, 30 + rid),
                           params=SamplingParams(max_new_tokens=6)))
    eng.step()                          # the three final chunks
    before = dict(eng.stats)
    eng.step()                          # one multi-step dispatch
    assert eng.stats["decode_steps"] - before["decode_steps"] == 1
    emitted = eng.stats["emitted_tokens"] - before["emitted_tokens"]
    assert emitted == 3 * 4
    assert eng.stats["step_tokens"] - before["step_tokens"] == emitted
    assert eng.stats["step_slots"] - before["step_slots"] == 4 * 4


def _attn(eng) -> tuple:
    return tuple(eng.stats[k] for k in ("attn_kernel_steps",
                                        "attn_blocks_read",
                                        "attn_blocks_table"))


@pytest.mark.parametrize("use_pallas,want", [
    # the reference gathers every padded row's whole table
    (False, (0, 15, 15)),
    # the kernel reads each padded row's live blocks
    (True, (4, 10, 15)),
])
def test_attention_counters_hand_count(setup, use_pallas, want):
    import dataclasses

    cfg, params = setup
    eng = _engine(dataclasses.replace(cfg, use_pallas=use_pallas), params)
    eng.submit(Request(0, _prompt(cfg, 9, 6),
                       params=SamplingParams(max_new_tokens=8)))
    for _ in range(3):
        eng.step()
    # chunks 0-3, 4-7 and the final 8: one row at last positions 3, 7
    # and 8, tables 1, 2 and 4 wide (pow 2 of 1, 2, 3 live blocks)
    eng.submit(Request(1, _prompt(cfg, 2, 7),
                       params=SamplingParams(max_new_tokens=8)))
    eng.step()
    # row 0 decodes at 9 (3 live blocks), row 1's 2-token chunk ends at
    # 1 (1 block): 2 rows x a 4-wide table
    # table 1 + 2 + 4 + 8 = 15; live 1 + 2 + 3 + (3 + 1) = 10
    assert _attn(eng) == want
    assert eng.snapshot()["attn_blocks_table"] == 15


def test_attention_counters_on_the_multistep_path(setup):
    import dataclasses

    cfg, params = setup
    eng = _engine(dataclasses.replace(cfg, use_pallas=True), params,
                  host_stride=4)
    for rid in range(3):
        eng.submit(Request(rid, _prompt(cfg, 4, 30 + rid),
                           params=SamplingParams(max_new_tokens=6)))
    eng.step()                          # the three final chunks
    before = _attn(eng)
    eng.step()                          # one multi-step dispatch
    # 4 loop iterations over 4 padded rows at positions 4-7 (the padding
    # row stays at 4), a 2-wide table, both blocks live for every row
    assert tuple(a - b for a, b in zip(_attn(eng), before)) == (4, 32, 32)


# ---------------------------------------------------------------------------
# live blocks
# ---------------------------------------------------------------------------
def test_live_blocks_track_store_operations(setup):
    cfg, params = setup
    st = PagedKVStore(params, cfg, n_slots=3, max_len=32, block_size=4,
                      num_blocks=8)
    toks = _prompt(cfg, 12, 7)
    checks = []

    def check():
        assert st.live_blocks == _live_brute(st)
        checks.append(st.live_blocks)

    steps = [
        lambda: st.alloc_blocks(0, 9),                  # 3 blocks
        lambda: st.ensure_capacity(0, 13),              # grow to 4
        lambda: st.rewind(0, 9),                        # back to 3
        lambda: st.release(0, publish_tokens=toks),     # the trie keeps 3
        lambda: st.adopt_prefix(1, np.append(toks, 1)),     # maps them
        lambda: st.adopt_prefix(2, np.append(toks, [1, 2])),  # shares
        lambda: st.cow_for_write(1, 8, 8),              # slot 1 copies one
        lambda: st.release(1),
        lambda: st.release(2),
        lambda: st.alloc_blocks(0, 32),                 # 8: evicts the trie
        lambda: st.release(0),
    ]
    for op in steps:
        op()
        check()
    assert checks == [3, 4, 3, 0, 3, 3, 4, 3, 0, 8, 0]
    assert st.prefix_evictions == 3
    assert st.live_peak == 8
    st.reset_live_peak()
    assert st.live_peak == st.live_blocks == 0
    assert st.usage()["live_peak"] == 0 and st.usage()["live_blocks"] == 0
    assert max(checks) == 8


def test_live_blocks_through_preemption_rewind_and_eviction(setup):
    cfg, params = setup
    rng = np.random.default_rng(9)
    # a repeating prefix gives the prompt-lookup drafter matches
    shared = np.tile(rng.integers(2, cfg.vocab_size, 4), 4).astype(np.int32)
    eng = _engine(cfg, params, n_slots=3, max_len=48, num_blocks=14,
                  chunk_size=8)
    reqs = []
    for rid in range(6):
        prompt = np.concatenate(
            [shared, rng.integers(2, cfg.vocab_size, 3).astype(np.int32)])
        sp = SamplingParams(max_new_tokens=16, spec_k=3 if rid % 2 else 0)
        reqs.append(Request(rid, prompt, params=sp))
        eng.submit(reqs[-1])
    peak = 0
    while eng.has_work:
        eng.step()
        assert eng.store.live_blocks == _live_brute(eng.store)
        peak = max(peak, eng.store.live_blocks)
        assert eng.store.live_peak >= eng.store.live_blocks
    assert all(r.done for r in reqs)
    s = eng.snapshot()
    assert s["preemptions"] > 0 and s["prefix_hits"] > 0, s
    assert s["drafted"] > 0
    assert eng.store.live_blocks == 0
    assert s["live_peak"] == eng.store.live_peak >= peak > 0
