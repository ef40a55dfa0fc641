"""Paged-attention-native RAGGED decode + the Sampler protocol.

Property tests (hypothesis, or the deterministic shim on bare envs):

  - paged attention == dense attention over the same K/V, across ragged
    lengths, block sizes and GQA group widths — at the op level (the
    ref twin vs an independently-built dense view) and at the kernel
    level (Pallas interpret vs the ref twin);
  - RAGGED positions: one call with a per-row ``positions`` vector
    equals B independent per-row calls at each row's scalar position —
    the invariant the fused engine step rests on;
  - engine-level: paged == dense generations across random traces,
    block-boundary prompt lengths, and post-preemption re-prefill (all
    through the fused one-step-per-iteration scheduler);
  - every Sampler at temperature -> 0 equals the fused argmax
    comparator (Theorem 1), including lowest-index tie-breaking;
  - multi-token steps as the engine builds them (decode rows beside
    prefill chunks, narrow final chunks, tables padded past every
    extent, extents off the copy group): kernel == ref twin, padded
    query columns bit for bit their row's last real query;
  - dispatch: on the chip ``use_pallas=False`` runs the compiled kernel
    where it applies and the ref twin elsewhere; off it nothing changed.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:                     # bare env: deterministic shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.configs import ARCHS, smoke_config
from repro.kernels import ops, ref
from repro.kernels import paged_attention as pa
from repro.models import lm
from repro.serve.engine import Request, ServeEngine
from repro.serve.sampler import (
    Greedy,
    SoftmaxBaseline,
    Temperature,
    TopK,
    resolve,
)

KEY = jax.random.PRNGKey(0)


def _mk(arch="qwen3-0.6b", key=KEY):
    cfg = smoke_config(ARCHS[arch])
    return cfg, lm.init_params(cfg, key)


def _pool_case(rng, pos, bs, g, hkv=2, hd=16, b=3, spare=3):
    """Random pools + per-row block tables covering [0, pos]."""
    nb = pos // bs + 1
    nblocks = b * nb + spare
    q = jnp.asarray(rng.normal(size=(b, g * hkv, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nblocks, bs, hkv, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nblocks, bs, hkv, hd)), jnp.float32)
    bt = np.stack([rng.choice(nblocks, nb, replace=False) for _ in range(b)])
    return q, kp, vp, jnp.asarray(bt, jnp.int32)


def _dense_view_attention(q, kp, vp, bt, pos, max_len):
    """Independent oracle: scatter the blocks into a (B, max_len) dense
    cache and run plain masked softmax attention over it."""
    b, hq, hd = q.shape
    bs, hkv = kp.shape[1], kp.shape[2]
    nb = bt.shape[1]
    k = np.zeros((b, max_len, hkv, hd), np.float32)
    v = np.zeros((b, max_len, hkv, hd), np.float32)
    for i in range(b):
        for j in range(nb):
            k[i, j * bs:(j + 1) * bs] = np.asarray(kp)[bt[i, j]]
            v[i, j * bs:(j + 1) * bs] = np.asarray(vp)[bt[i, j]]
    g = hq // hkv
    qg = np.asarray(q).reshape(b, hkv, g, hd)
    sc = np.einsum("bkgh,bskh->bkgs", qg, k) / np.sqrt(hd)
    sc = np.where((np.arange(max_len) <= pos)[None, None, None, :],
                  sc, -np.inf)
    pr = np.exp(sc - sc.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    return np.einsum("bkgs,bskh->bkgh", pr, v).reshape(b, hq, hd)


# ---------------------------------------------------------------------------
# Op level: ref twin and Pallas kernel vs an independent dense view
# ---------------------------------------------------------------------------
@settings(max_examples=12, deadline=None)
@given(st.integers(min_value=0, max_value=47),
       st.sampled_from([4, 8, 16]),
       st.sampled_from([1, 2, 4]))
def test_paged_ref_matches_dense_view(pos, bs, g):
    rng = np.random.default_rng([pos, bs, g])
    q, kp, vp, bt = _pool_case(rng, pos, bs, g)
    got = np.asarray(ref.paged_attention(q, kp, vp, bt, jnp.int32(pos)))
    want = _dense_view_attention(q, kp, vp, np.asarray(bt), pos,
                                 max_len=(pos // bs + 1) * bs)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=0, max_value=40),
       st.sampled_from([4, 8]),
       st.sampled_from([1, 2]))
def test_paged_kernel_matches_ref(pos, bs, g):
    rng = np.random.default_rng([7, pos, bs, g])
    q, kp, vp, bt = _pool_case(rng, pos, bs, g)
    # pad the table to a pow-2 column count like the engine does: the
    # repeated columns sit past pos and the mask must discard them
    nb = bt.shape[1]
    nbb = 1 << (nb - 1).bit_length()
    btp = jnp.concatenate(
        [bt, jnp.repeat(bt[:, :1], nbb - nb, axis=1)], axis=1)
    r = np.asarray(ref.paged_attention(q, kp, vp, btp, jnp.int32(pos)))
    p = np.asarray(ops.paged_attention(q, kp, vp, btp, jnp.int32(pos),
                                       use_pallas=True, interpret=True))
    np.testing.assert_allclose(p, r, rtol=2e-5, atol=2e-6)


def _ragged_case(rng, positions, bs, g, hkv=2, hd=16, spare=3):
    """Pools + per-row tables where every row sits at its OWN position;
    rows shorter than the widest pad their table with their first block
    (exactly what the engine's ragged block_table builds)."""
    b = len(positions)
    nb = max(positions) // bs + 1
    nblocks = b * nb + spare
    q = jnp.asarray(rng.normal(size=(b, g * hkv, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nblocks, bs, hkv, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nblocks, bs, hkv, hd)), jnp.float32)
    rows = []
    for p in positions:
        own = rng.choice(nblocks, p // bs + 1, replace=False)
        rows.append(np.concatenate(
            [own, np.repeat(own[:1], nb - len(own))]))
    return q, kp, vp, jnp.asarray(np.stack(rows), jnp.int32)


@settings(max_examples=10, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=47),
                min_size=2, max_size=5),
       st.sampled_from([4, 8, 16]),
       st.sampled_from([1, 2, 4]))
def test_paged_ref_ragged_positions_row_equivalence(positions, bs, g):
    """One ragged call == B independent per-row calls at scalar
    positions: the op is row-separable, so slots at arbitrary sequence
    lengths fuse into one step without changing any row's math."""
    rng = np.random.default_rng([bs, g] + list(positions))
    q, kp, vp, bt = _ragged_case(rng, positions, bs, g)
    pos = jnp.asarray(positions, jnp.int32)
    got = np.asarray(ref.paged_attention(q, kp, vp, bt, pos))
    for i, p in enumerate(positions):
        row = np.asarray(ref.paged_attention(
            q[i:i + 1], kp, vp, bt[i:i + 1], jnp.int32(p)))
        np.testing.assert_allclose(got[i], row[0], rtol=1e-6, atol=1e-7)


@settings(max_examples=8, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=40),
                min_size=2, max_size=4),
       st.sampled_from([4, 8]),
       st.sampled_from([1, 2]))
def test_paged_kernel_ragged_matches_ref(positions, bs, g):
    """The Pallas kernel's per-row scalar-prefetch position mask agrees
    with the ref twin on ragged batches (pow-2-padded tables included)."""
    rng = np.random.default_rng([11, bs, g] + list(positions))
    q, kp, vp, bt = _ragged_case(rng, positions, bs, g)
    nb = bt.shape[1]
    nbb = 1 << (nb - 1).bit_length()
    btp = jnp.concatenate(
        [bt, jnp.repeat(bt[:, :1], nbb - nb, axis=1)], axis=1)
    pos = jnp.asarray(positions, jnp.int32)
    r = np.asarray(ref.paged_attention(q, kp, vp, btp, pos))
    p = np.asarray(ops.paged_attention(q, kp, vp, btp, pos,
                                       use_pallas=True, interpret=True))
    np.testing.assert_allclose(p, r, rtol=2e-5, atol=2e-6)


def test_paged_scalar_position_broadcasts():
    """A scalar position still broadcasts to the whole batch (the legacy
    uniform-batch call signature keeps working)."""
    rng = np.random.default_rng(3)
    q, kp, vp, bt = _pool_case(rng, 13, 8, g=2)
    vec = jnp.full((q.shape[0],), 13, jnp.int32)
    a = np.asarray(ref.paged_attention(q, kp, vp, bt, jnp.int32(13)))
    b = np.asarray(ref.paged_attention(q, kp, vp, bt, vec))
    np.testing.assert_array_equal(a, b)
    pa = np.asarray(ops.paged_attention(q, kp, vp, bt, jnp.int32(13),
                                        use_pallas=True, interpret=True))
    pb = np.asarray(ops.paged_attention(q, kp, vp, bt, vec,
                                        use_pallas=True, interpret=True))
    np.testing.assert_array_equal(pa, pb)


def test_paged_kernel_block_boundaries():
    """Exact block-boundary positions: last row of a block, first row of
    the next, single-block, and pow-2-padded tables."""
    bs = 8
    for pos in (0, bs - 1, bs, 2 * bs - 1, 2 * bs, 3 * bs):
        rng = np.random.default_rng(pos)
        q, kp, vp, bt = _pool_case(rng, pos, bs, g=2)
        r = np.asarray(ref.paged_attention(q, kp, vp, bt, jnp.int32(pos)))
        p = np.asarray(ops.paged_attention(q, kp, vp, bt, jnp.int32(pos),
                                           use_pallas=True, interpret=True))
        np.testing.assert_allclose(p, r, rtol=2e-5, atol=2e-6)


def _window_case(rng, rows, t, bs, nb=None, g=2, hkv=2, hd=16):
    """A multi-token step as the engine builds it: ``rows`` gives each
    row's (largest position, real width); a row's padding repeats its
    last (query, position), and its table, padded with its own first
    block, is ``nb`` wide (the pow-2 cover of the widest row if None)."""
    need = [last // bs + 1 for last, _ in rows]
    nb = nb or 1 << (max(need) - 1).bit_length()
    nblocks = sum(need) + 3
    perm = rng.permutation(nblocks)
    tables, pos, q = [], [], rng.normal(size=(len(rows), t, g * hkv, hd))
    for r, ((last, w), n) in enumerate(zip(rows, need)):
        own = perm[sum(need[:r]):sum(need[:r]) + n]
        tables.append(np.concatenate([own, np.repeat(own[:1], nb - n)]))
        pos.append(last - w + 1 + np.minimum(np.arange(t), w - 1))
        q[r, w:] = q[r, w - 1]
    kp = rng.normal(size=(nblocks, bs, hkv, hd))
    vp = rng.normal(size=(nblocks, bs, hkv, hd))
    return (jnp.asarray(q, jnp.float32), jnp.asarray(kp, jnp.float32),
            jnp.asarray(vp, jnp.float32), jnp.asarray(np.stack(tables),
                                                      jnp.int32),
            jnp.asarray(np.stack(pos), jnp.int32))


# case -> (rows as (largest position, real width), T, block size, table
# width (None: pow-2 cover), mode, window).  Copy groups are 16 pages.
_WINDOW_CASES = {
    # decode rows riding a step widened by 64-wide prefill chunks
    "decode_and_64_chunks": ([(37, 1), (100, 64), (5, 1), (130, 64),
                              (16, 1)], 64, 8, None, "exact", None),
    # final chunks narrower than the step's width
    "narrow_final_chunks": ([(40, 16), (23, 5), (9, 1), (63, 11), (2, 3)],
                            16, 2, None, "exact", None),
    # a table padded far past every row's extent
    "table_past_every_extent": ([(3, 1), (17, 4), (30, 2)], 4, 8, 64,
                                "exact", None),
    # extents at block edges that are and are not a multiple of a group:
    # 16, 17, 32 and 33 pages, and one page
    "block_and_group_edges": ([(31, 1), (32, 2), (63, 1), (64, 2), (65, 2),
                               (0, 1), (3, 2)], 2, 2, None, "exact", None),
    "window": ([(37, 1), (40, 8), (5, 1), (19, 8)], 8, 2, None, "exact", 5),
    "maxonly": ([(37, 1), (40, 8), (5, 1), (19, 8)], 8, 2, None, "maxonly",
                None),
    "pseudo": ([(37, 1), (40, 8), (5, 1), (19, 8)], 8, 2, None, "pseudo",
               None),
}


@pytest.mark.parametrize("case", sorted(_WINDOW_CASES))
def test_paged_kernel_windows_match_ref(case):
    """The kernel (interpret mode) equals the ref twin on the steps the
    engine builds — decode rows computed once for their one real query,
    chunk rows over their whole window, live pages only, in copy groups
    that need not divide a row's extent — and every padded query column
    is its row's last real query, bit for bit."""
    rows, t, bs, nb, mode, window = _WINDOW_CASES[case]
    rng = np.random.default_rng(sorted(_WINDOW_CASES).index(case))
    q, kp, vp, bt, pos = _window_case(rng, rows, t, bs, nb)
    r = np.asarray(ref.paged_attention(q, kp, vp, bt, pos,
                                       attn_approx=mode, window=window))
    k = np.asarray(pa.paged_attention(q, kp, vp, bt, pos, interpret=True,
                                      attn_approx=mode, window=window))
    np.testing.assert_allclose(k, r, rtol=5e-5, atol=5e-5)
    for row, (_, w) in enumerate(rows):
        for col in range(w, t):
            np.testing.assert_array_equal(k[row, col], k[row, w - 1])


# ---------------------------------------------------------------------------
# Dispatch: which twin ops.paged_attention runs
# ---------------------------------------------------------------------------
@pytest.fixture
def twins(monkeypatch):
    """Both twins replaced by spies that record which one the dispatch
    picked, and how (nothing runs or compiles)."""
    calls = []
    monkeypatch.setattr(pa, "paged_attention",
                        lambda *a, **kw: calls.append(
                            ("kernel", kw["interpret"])))
    monkeypatch.setattr(ref, "paged_attention",
                        lambda *a, **kw: calls.append(("ref", None)))
    return calls


def _dispatch(t, **kw):
    q = jnp.zeros((2, 4, 8) if t == 1 else (2, t, 4, 8), jnp.float32)
    pool = jnp.zeros((4, 4, 2, 8), jnp.float32)
    pos = jnp.zeros((2,) if t == 1 else (2, t), jnp.int32)
    ops.paged_attention(q, pool, pool, jnp.zeros((2, 2), jnp.int32), pos,
                        **kw)


@pytest.mark.parametrize("mode,t,split,want", [
    ("exact", 1, False, ("kernel", False)),
    ("exact", 64, False, ("kernel", False)),
    ("pseudo", 128, False, ("kernel", False)),
    ("maxonly", 8, False, ("kernel", False)),
    ("base2", 1, False, ("ref", None)),
    ("pwl", 64, False, ("ref", None)),
    ("exact", 256, False, ("ref", None)),
    ("exact", 1, True, ("ref", None)),
])
def test_dispatch_on_the_chip(twins, monkeypatch, mode, t, split, want):
    """With the backend read as the chip's, ``use_pallas=False`` runs the
    compiled kernel for the modes it lowers at T <= 128 over a whole
    pool, and the reference otherwise."""
    from types import SimpleNamespace

    from repro.parallel import env

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = SimpleNamespace(shape={"data": 1, "model": 2}) if split else None
    with env.use_mesh(mesh):
        _dispatch(t, attn_approx=mode)
        assert pa.kernel_runs(t, attn_approx=mode) == (want[0] == "kernel")
    assert twins == [want]


@pytest.mark.parametrize("use_pallas,want", [(False, ("ref", None)),
                                             (True, ("kernel", True))])
def test_dispatch_off_the_chip(twins, use_pallas, want):
    """On the CPU nothing changed: the reference by default, the kernel
    in interpret mode when ``use_pallas`` asks for it."""
    for t in (1, 64):
        _dispatch(t, use_pallas=use_pallas)
    assert twins == [want, want]


# ---------------------------------------------------------------------------
# Engine level: paged == dense generations (the tokens are the contract)
# ---------------------------------------------------------------------------
def _run(params, cfg, prompts, max_new=5, **kw):
    eng = ServeEngine(params, cfg, eos_id=1, **kw)
    reqs = [Request(i, p.copy(), max_new) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return [r.generated for r in reqs], eng


@settings(max_examples=3, deadline=None)
@given(st.lists(st.integers(min_value=3, max_value=25),
                min_size=2, max_size=4),
       st.sampled_from([4, 8]))
def test_engine_paged_equals_dense_ragged(plens, bs):
    cfg, params = _mk()
    rng = np.random.default_rng([bs] + list(plens))
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in plens]
    dense, _ = _run(params, cfg, prompts, max_new=5,
                    n_slots=2, max_len=48, kv_layout="dense")
    paged, eng = _run(params, cfg, prompts, max_new=5,
                      n_slots=2, max_len=48, kv_layout="paged",
                      block_size=bs)
    assert paged == dense
    assert eng.store.allocator.n_free == eng.store.allocator.num_blocks


def test_engine_block_boundary_prompts():
    """Prompt lengths straddling block boundaries; generation crosses
    further boundaries mid-decode."""
    cfg, params = _mk()
    bs = 8
    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (bs - 1, bs, bs + 1, 2 * bs)]
    dense, _ = _run(params, cfg, prompts, max_new=2 * bs + 2,
                    n_slots=2, max_len=64, kv_layout="dense")
    paged, _ = _run(params, cfg, prompts, max_new=2 * bs + 2,
                    n_slots=2, max_len=64, kv_layout="paged", block_size=bs)
    assert paged == dense


def test_engine_post_preemption_reprefill_matches():
    """A pool sized for one full sequence forces preempt -> re-prefill;
    the re-prefilled request must continue token-exactly."""
    cfg, params = _mk()
    rng = np.random.default_rng(23)
    prompts = [rng.integers(0, cfg.vocab_size, size=9).astype(np.int32)
               for _ in range(3)]
    dense, _ = _run(params, cfg, prompts, max_new=14,
                    n_slots=2, max_len=64, kv_layout="dense")
    tight, eng = _run(params, cfg, prompts, max_new=14,
                      n_slots=2, max_len=64, kv_layout="paged",
                      block_size=8, num_blocks=5)
    assert tight == dense
    assert eng.stats["preemptions"] >= 1
    assert eng.store.allocator.n_free == 5


# ---------------------------------------------------------------------------
# Sampler protocol: temperature -> 0 IS the comparator (Theorem 1)
# ---------------------------------------------------------------------------
def _tied_head_params(cfg, params, dup_pairs):
    """Duplicate lm_head columns so those vocab ids tie EXACTLY."""
    w = np.array(lm.lm_head_weight(params, cfg))
    for lo, hi in dup_pairs:
        w[:, hi] = w[:, lo]
    p = dict(params)
    if cfg.tie_embeddings:
        p["embed"] = jnp.asarray(w.T)
    else:
        p["lm_head"] = jnp.asarray(w)
    return p


@pytest.mark.parametrize("sampler", [
    Greedy(), Greedy("fused"), SoftmaxBaseline(),
    TopK(8, temperature=0.0), TopK(8, temperature=-1.0),
    Temperature(0.0), Temperature(-1.0),
])
def test_every_sampler_at_t0_is_the_comparator(sampler):
    """head() + pick() at temperature <= 0 == argmax of the logits, with
    exactly tied columns resolving to the LOWEST vocab index — the fused
    comparator's contract, uniform across the whole Sampler zoo."""
    cfg, params = _mk()
    params = _tied_head_params(cfg, params, [(5, 99), (5, 200)])
    rng = np.random.default_rng(31)
    w = np.asarray(lm.lm_head_weight(params, cfg), np.float32)
    h = rng.normal(size=(6, cfg.d_model)).astype(np.float32)
    h[-1] = 8.0 * w[:, 5]       # forces the 3-way tie {5, 99, 200} to win
    h = jnp.asarray(h)
    want = np.argmax(np.asarray(h) @ w, axis=-1)
    assert want[-1] == 5        # argmax oracle itself picks the lowest id

    out = sampler.head(params, cfg, h)
    out = tuple(np.asarray(o) for o in out) if isinstance(out, tuple) \
        else np.asarray(out)
    got = [sampler.pick(out, row, np.random.default_rng(0))
           for row in range(h.shape[0])]
    np.testing.assert_array_equal(got, want)
    assert 99 not in got and 200 not in got


@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=2, max_value=32))
def test_topk_head_prefix_of_comparator(k):
    """The k-winner bus's survivor 0 is the argmax for every k."""
    cfg, params = _mk()
    rng = np.random.default_rng(41)
    h = jnp.asarray(rng.normal(size=(4, cfg.d_model)), jnp.float32)
    s = TopK(k, temperature=0.0)
    vals, idxs = s.head(params, cfg, h)
    w = np.asarray(lm.lm_head_weight(params, cfg), np.float32)
    want = np.argmax(np.asarray(h) @ w, axis=-1)
    np.testing.assert_array_equal(np.asarray(idxs)[:, 0], want)


def test_resolve_is_the_only_string_switch():
    """resolve() maps every legacy head_mode/top_k/temperature triple and
    rejects the combinations the engine used to guard inline."""
    cfg, _ = _mk()
    assert resolve("reduced") == Greedy("reduced")
    assert resolve("fused", top_k=4, temperature=0.5) == \
        TopK(4, 0.5, "fused")
    assert resolve("softmax") == SoftmaxBaseline()
    assert resolve("temperature", temperature=0.7) == Temperature(0.7)
    assert resolve(Temperature(0.3)) == Temperature(0.3)
    with pytest.raises(ValueError, match="top_k"):
        resolve("reduced", top_k=500, cfg=cfg)
    with pytest.raises(ValueError, match="top_k sampling"):
        resolve("softmax", top_k=4, cfg=cfg)
    # the k-winner bus HAS a sharded form (per-shard top-k + (val, idx)
    # table combine) — resolves instead of rejecting
    assert resolve("sharded", top_k=4, cfg=cfg) == TopK(4, 1.0, "sharded")
    # host-only fields never fragment a cohort / jit cache
    assert TopK(4, 0.9).device_form() == TopK(4, 1.0).device_form()
    assert Temperature(0.1).device_form() == Temperature(2.0).device_form()
