"""Approximate-attention subsystem: catalog, kernel twins, engine, probe.

What the catalog must guarantee, level by level:

  - catalog/resolve: unknown names and degenerate windows fail loudly;
    the weight functions approximate exp within their documented
    resolution, and ``attn_weights`` matches the paper units in
    ``core/softmax_variants.py`` where they overlap (pseudo) and plain
    ``jax.nn.softmax`` for exact;
  - kernel twins: Pallas (interpret) == ref for EVERY (variant, window)
    point — ragged positions, pow-2-padded tables, permuted physical
    blocks, multi-token windows — with per-variant tolerances (LUT
    variants are bounded by their bin width, not float rounding);
  - windowed masks: paged == ref == an independent dense-slice oracle
    across windows straddling block boundaries;
  - maxonly IS argmax: the output is exactly the V row of the highest
    (first, on ties) valid score;
  - engine: ``attn_approx='exact'`` is BIT-identical to the stock
    engine — plain, under spec_k, and under host_stride; approximate
    variants serve end-to-end, surface in snapshot(), and the
    params/engine mode mismatch raises at submit;
  - probe: the report carries the documented schema and the exact arm
    reports zero divergence.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, smoke_config
from repro.core import attn_approx as approx
from repro.core import softmax_variants as sv
from repro.kernels import ops, ref
from repro.models import lm
from repro.serve.engine import Request, ServeEngine
from repro.serve.params import SamplingParams

KEY = jax.random.PRNGKey(0)

# paged-vs-ref tolerance per variant: exact/pseudo/maxonly differ only
# by float rounding (their carries are homomorphic in the rescale
# base); base2/pwl evaluate their LUT at the block-running max instead
# of the global max, so agreement is bounded by one LUT bin (~0.4%
# relative) / one chord error — still single-shot, never compounding.
TOL = {"exact": 5e-5, "pseudo": 5e-5, "maxonly": 5e-5,
       "base2": 2e-3, "pwl": 2e-3}


def _mk(arch="qwen3-0.6b", key=KEY):
    cfg = smoke_config(ARCHS[arch])
    return cfg, lm.init_params(cfg, key)


def _pool_case(rng, pos, bs, g, hkv=2, hd=16, b=3, spare=3):
    nb = pos // bs + 1
    nblocks = b * nb + spare
    q = jnp.asarray(rng.normal(size=(b, g * hkv, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nblocks, bs, hkv, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nblocks, bs, hkv, hd)), jnp.float32)
    bt = np.stack([rng.choice(nblocks, nb, replace=False)
                   for _ in range(b)])
    return q, kp, vp, jnp.asarray(bt, jnp.int32)


# ---------------------------------------------------------------------------
# Catalog / resolve
# ---------------------------------------------------------------------------
def test_resolve_validates():
    assert approx.resolve("exact", None) == ("exact", None)
    assert approx.resolve("maxonly", 8) == ("maxonly", 8)
    with pytest.raises(ValueError, match="base2"):
        approx.resolve("nope", None)       # error names the catalog
    for bad in (0, -3):
        with pytest.raises(ValueError):
            approx.resolve("exact", bad)
    assert set(approx.VARIANTS) == set(approx.CATALOG) == {
        "exact", "base2", "pseudo", "pwl", "maxonly"}


def test_catalog_metadata():
    assert not approx.CATALOG["exact"].exp_free
    for name in ("base2", "pseudo", "pwl", "maxonly"):
        assert approx.CATALOG[name].exp_free, name
    # order preservation is what makes greedy-argmax comparisons
    # meaningful for every variant
    assert all(v.order_preserving for v in approx.CATALOG.values())


def test_weight_exp_tracks_exp():
    """Each f approximates its target on the online-carry domain
    (d <= 0) within the documented resolution."""
    d = jnp.linspace(-20.0, 0.0, 4001)
    e = np.exp(np.asarray(d))
    for name, tol in (("base2", 4e-3), ("pwl", 3e-4)):
        got = np.asarray(approx.weight_exp(d, name))
        assert np.max(np.abs(got - e)) < tol, name
    # pseudo is 2^d by design — a DIFFERENT curve, not an exp estimate
    np.testing.assert_allclose(np.asarray(approx.weight_exp(d, "pseudo")),
                               np.exp2(np.asarray(d)), rtol=1e-6)
    with pytest.raises(ValueError):
        approx.weight_exp(d, "maxonly")    # no weight function exists


def test_attn_weights_matches_paper_units():
    rng = np.random.default_rng(0)
    s = jnp.asarray(rng.normal(size=(5, 33)) * 3, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(approx.attn_weights(s, "exact")),
        np.asarray(jax.nn.softmax(s, axis=-1)), rtol=1e-6, atol=1e-7)
    # pseudo IS the pseudo-softmax unit of core/softmax_variants.py
    np.testing.assert_allclose(
        np.asarray(approx.attn_weights(s, "pseudo")),
        np.asarray(sv.pseudo_softmax_unit(s)), rtol=1e-5, atol=1e-6)
    for name in ("base2", "pwl"):
        w = np.asarray(approx.attn_weights(s, name))
        np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-5)
        assert float(approx.score_error(s, name)) < 5e-3, name
    # maxonly: one-hot at the FIRST max (argmax tie semantics)
    tied = jnp.asarray([[1.0, 3.0, 3.0, 0.0]])
    w = np.asarray(approx.attn_weights(tied, "maxonly"))
    np.testing.assert_array_equal(w, [[0.0, 1.0, 0.0, 0.0]])


def test_base2_exp_raw_is_shared_helper():
    """Satellite check: the catalog's base2 path IS the paper unit's
    LUT helper (one export point, no duplicated tables)."""
    x = jnp.linspace(-15.0, 4.0, 997)
    np.testing.assert_array_equal(
        np.asarray(approx.weight_exp(x, "base2")),
        np.asarray(sv.base2_exp_raw(x)))
    assert sv.base2_frac_lut().shape == (256,)


# ---------------------------------------------------------------------------
# Kernel twins: every (variant, window) point, ragged + padded tables
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("variant", approx.VARIANTS)
@pytest.mark.parametrize("window", [None, 1, 7, 8, 9, 100])
def test_paged_kernel_matches_ref_variant_window(variant, window):
    """Pallas (interpret) == ref per (variant, window) on a ragged
    batch with pow-2-padded, permuted-physical-block tables — windows
    chosen to straddle the bs=8 block boundary."""
    bs, g = 8, 2
    positions = [3, 8, 23, 30]
    rng = np.random.default_rng([hash(variant) % 1000, window or 0])
    b = len(positions)
    nb = max(positions) // bs + 1
    nblocks = b * nb + 3
    q = jnp.asarray(rng.normal(size=(b, g * 2, 16)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nblocks, bs, 2, 16)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nblocks, bs, 2, 16)), jnp.float32)
    rows = []
    for p in positions:
        own = rng.choice(nblocks, p // bs + 1, replace=False)
        rows.append(np.concatenate([own, np.repeat(own[:1], nb - len(own))]))
    bt = jnp.asarray(np.stack(rows), jnp.int32)
    nbb = 1 << (nb - 1).bit_length()
    btp = jnp.concatenate(
        [bt, jnp.repeat(bt[:, :1], nbb - nb, axis=1)], axis=1)
    pos = jnp.asarray(positions, jnp.int32)
    r = np.asarray(ref.paged_attention(q, kp, vp, btp, pos,
                                       attn_approx=variant, window=window))
    p = np.asarray(ops.paged_attention(q, kp, vp, btp, pos,
                                       use_pallas=True, interpret=True,
                                       attn_approx=variant, window=window))
    np.testing.assert_allclose(p, r, rtol=TOL[variant], atol=TOL[variant])


@pytest.mark.parametrize("variant", approx.VARIANTS)
def test_paged_kernel_multi_token_variant(variant):
    """The (B, T) multi-token form (spec windows / prefill chunks)
    honors variant + window identically in both twins."""
    rng = np.random.default_rng(42)
    b, t, g, hkv, hd, bs = 2, 3, 2, 2, 16, 8
    nb, nblocks = 4, 10
    q = jnp.asarray(rng.normal(size=(b, t, g * hkv, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nblocks, bs, hkv, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nblocks, bs, hkv, hd)), jnp.float32)
    bt = jnp.asarray(np.stack([rng.choice(nblocks, nb, replace=False)
                               for _ in range(b)]), jnp.int32)
    pos = (jnp.asarray([[13], [26]], jnp.int32)
           + jnp.arange(t)[None, :])
    for window in (None, 5):
        r = np.asarray(ref.paged_attention(
            q, kp, vp, bt, pos, attn_approx=variant, window=window))
        p = np.asarray(ops.paged_attention(
            q, kp, vp, bt, pos, use_pallas=True, interpret=True,
            attn_approx=variant, window=window))
        np.testing.assert_allclose(p, r, rtol=TOL[variant],
                                   atol=TOL[variant])


@pytest.mark.parametrize("variant", ["base2", "pwl"])
def test_compiled_kernel_refuses_lut_variants(variant):
    """base2/pwl look their weights up in a table, a gather Mosaic does
    not lower: the compiled kernel raises its own clear error before
    Mosaic's, and never substitutes the reference."""
    rng = np.random.default_rng(0)
    q, kp, vp, bt = _pool_case(rng, pos=20, bs=8, g=2)
    pos = jnp.full((q.shape[0],), 20, jnp.int32)
    with pytest.raises(NotImplementedError, match=variant):
        ops.paged_attention(q, kp, vp, bt, pos, use_pallas=True,
                            interpret=False, attn_approx=variant)


def test_windowed_paged_matches_dense_slice_oracle():
    """paged(window=w) == plain softmax attention over the dense slice
    [pos-w+1, pos] — an oracle built independently of both twins."""
    bs, g, hd, hkv = 8, 2, 16, 2
    pos = 29
    rng = np.random.default_rng(7)
    q, kp, vp, bt = _pool_case(rng, pos, bs, g, hkv=hkv, hd=hd)
    max_len = (pos // bs + 1) * bs
    b, hq = q.shape[0], g * hkv
    k = np.zeros((b, max_len, hkv, hd), np.float32)
    v = np.zeros((b, max_len, hkv, hd), np.float32)
    for i in range(b):
        for j in range(bt.shape[1]):
            k[i, j * bs:(j + 1) * bs] = np.asarray(kp)[bt[i, j]]
            v[i, j * bs:(j + 1) * bs] = np.asarray(vp)[bt[i, j]]
    for w in (1, 7, 8, 9, 16, 100):       # straddle the block boundary
        lo = max(0, pos - w + 1)
        ks, vs = k[:, lo:pos + 1], v[:, lo:pos + 1]
        qg = np.asarray(q).reshape(b, hkv, g, hd)
        sc = np.einsum("bkgh,bskh->bkgs", qg, ks) / np.sqrt(hd)
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        pr /= pr.sum(-1, keepdims=True)
        want = np.einsum("bkgs,bskh->bkgh", pr, vs).reshape(b, hq, hd)
        for use_pallas in (False, True):
            got = np.asarray(ops.paged_attention(
                q, kp, vp, bt, jnp.int32(pos), use_pallas=use_pallas,
                interpret=True, window=w))
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_maxonly_is_argmax_select():
    """maxonly output == the V row of the first highest valid score —
    the comparator datapath, no weights anywhere."""
    bs, g, hd, hkv = 8, 2, 16, 2
    pos = 21
    rng = np.random.default_rng(11)
    q, kp, vp, bt = _pool_case(rng, pos, bs, g, hkv=hkv, hd=hd)
    b, hq = q.shape[0], g * hkv
    max_len = (pos // bs + 1) * bs
    k = np.zeros((b, max_len, hkv, hd), np.float32)
    v = np.zeros((b, max_len, hkv, hd), np.float32)
    for i in range(b):
        for j in range(bt.shape[1]):
            k[i, j * bs:(j + 1) * bs] = np.asarray(kp)[bt[i, j]]
            v[i, j * bs:(j + 1) * bs] = np.asarray(vp)[bt[i, j]]
    qg = np.asarray(q).reshape(b, hkv, g, hd)
    sc = np.einsum("bkgh,bskh->bkgs", qg, k[:, :pos + 1]) / np.sqrt(hd)
    sel = np.argmax(sc, axis=-1)           # first max, numpy semantics
    want = np.zeros((b, hkv, g, hd), np.float32)
    for i in range(b):
        for kv in range(hkv):
            for gg in range(g):
                want[i, kv, gg] = v[i, sel[i, kv, gg], kv]
    want = want.reshape(b, hq, hd)
    for use_pallas in (False, True):
        got = np.asarray(ops.paged_attention(
            q, kp, vp, bt, jnp.int32(pos), use_pallas=use_pallas,
            interpret=True, attn_approx="maxonly"))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Engine level
# ---------------------------------------------------------------------------
def _serve(params, cfg, prompts, sp, **kw):
    eng = ServeEngine(params, cfg, eos_id=1, **kw)
    reqs = [Request(i, p.copy(), params=sp) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return [list(r.generated) for r in reqs], eng


def _prompts(cfg, n=4, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size,
                         int(rng.integers(4, 20))).astype(np.int32)
            for _ in range(n)]


def test_engine_exact_is_bit_identical():
    """attn_approx='exact' replaces to an EQUAL frozen cfg: same jit
    caches, same tokens — plain, under spec_k, under host_stride."""
    cfg, params = _mk()
    prompts = _prompts(cfg)
    sp = SamplingParams(max_new_tokens=8)
    base, _ = _serve(params, cfg, prompts, sp, n_slots=2, max_len=64)
    got, eng = _serve(params, cfg, prompts, sp, n_slots=2, max_len=64,
                      attn_approx="exact")
    assert got == base
    assert eng.cfg == dataclasses.replace(cfg, attn_approx="exact")
    rep = [np.tile(np.arange(2, 6, dtype=np.int32), 4) for _ in range(3)]
    spp = SamplingParams(max_new_tokens=10, spec_k=4)
    b_spec, _ = _serve(params, cfg, rep, spp, n_slots=2, max_len=64)
    g_spec, e_spec = _serve(params, cfg, rep, spp, n_slots=2, max_len=64,
                            attn_approx="exact")
    assert g_spec == b_spec and e_spec.stats["accepted"] > 0
    b_ms, _ = _serve(params, cfg, prompts, sp, n_slots=2, max_len=64,
                     host_stride=4)
    g_ms, e_ms = _serve(params, cfg, prompts, sp, n_slots=2, max_len=64,
                        host_stride=4, attn_approx="exact")
    assert g_ms == b_ms == base
    assert e_ms.snapshot()["tokens_per_dispatch"] > 1.0


@pytest.mark.parametrize("variant", ["base2", "pseudo", "pwl", "maxonly"])
def test_engine_serves_variants(variant):
    """Every approximate mode serves end-to-end (valid streams, blocks
    returned) and surfaces in snapshot()."""
    cfg, params = _mk()
    prompts = _prompts(cfg, n=3, seed=1)
    sp = SamplingParams(max_new_tokens=6)
    got, eng = _serve(params, cfg, prompts, sp, n_slots=2, max_len=64,
                      attn_approx=variant, attn_window=16)
    assert all(len(g) >= 1 for g in got)
    snap = eng.snapshot()
    assert snap["attn_approx"] == variant and snap["attn_window"] == 16
    assert eng.store.allocator.n_free == eng.store.allocator.num_blocks


def test_engine_windowed_survives_preemption():
    """Sliding-window mask + tight pool (preempt -> re-prefill): the
    re-admitted request continues token-exactly vs a roomy pool.

    Both arms use CHUNKED prefill so the (re-)prefill rides the paged
    multi-token branch and sees the same window mask decode does —
    one-shot prefill is full-attention by design (the window is a
    decode-path knob), so its re-prefill would rebuild K/V from
    different hidden states."""
    cfg, params = _mk()
    rng = np.random.default_rng(23)
    prompts = [rng.integers(0, cfg.vocab_size, size=9).astype(np.int32)
               for _ in range(3)]
    sp = SamplingParams(max_new_tokens=14)
    roomy, _ = _serve(params, cfg, prompts, sp, n_slots=2, max_len=64,
                      block_size=8, chunk_size=8,
                      attn_approx="pseudo", attn_window=8)
    tight, eng = _serve(params, cfg, prompts, sp, n_slots=2, max_len=64,
                        block_size=8, num_blocks=5, chunk_size=8,
                        attn_approx="pseudo", attn_window=8)
    assert tight == roomy
    assert eng.stats["preemptions"] >= 1


def test_engine_mode_validation():
    cfg, params = _mk()
    with pytest.raises(ValueError):
        ServeEngine(params, cfg, attn_approx="nope")
    with pytest.raises(ValueError):
        ServeEngine(params, cfg, attn_window=0)
    # approximate modes need the paged path — dense layout refuses
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(params, cfg, kv_layout="dense", attn_approx="pseudo")
    with pytest.raises(ValueError):
        SamplingParams(attn_approx="nope")
    eng = ServeEngine(params, cfg, attn_approx="pseudo")
    with pytest.raises(ValueError, match="engine-wide"):
        eng.submit(Request(0, np.arange(3, dtype=np.int32),
                           params=SamplingParams(attn_approx="exact")))
    eng.submit(Request(1, np.arange(3, dtype=np.int32),
                       params=SamplingParams(attn_approx="pseudo")))


# ---------------------------------------------------------------------------
# Probe harness
# ---------------------------------------------------------------------------
def test_probe_report_schema():
    from repro import probe as probe_mod

    cfg, params = _mk()
    prompts = _prompts(cfg, n=3, seed=2)
    rep = probe_mod.run_probe(params, cfg, prompts,
                              variants=("pseudo", "maxonly"),
                              max_new_tokens=4, n_slots=2, max_len=64)
    assert rep["n_requests"] == 3 and rep["baseline"] == "exact"
    assert set(rep["variants"]) == {"exact", "pseudo", "maxonly"}
    ex = rep["variants"]["exact"]
    assert ex["divergence"] == 0.0 and ex["diverged_requests"] == 0
    assert ex["first_divergence"] == [None] * 3
    for name in ("pseudo", "maxonly"):
        row = rep["variants"][name]
        for k in ("divergence", "diverged_requests", "n_requests",
                  "first_divergence", "mean_first_divergence",
                  "score_error"):
            assert k in row, (name, k)
        assert 0.0 <= row["divergence"] <= 1.0
        assert len(row["first_divergence"]) == 3
        assert all(v >= 0.0 for v in row["score_error"].values())
    # a report parked on the engine rides snapshot() -> /v1/stats
    eng = ServeEngine(params, cfg, n_slots=2, max_len=64)
    eng.probe_report = rep
    assert eng.snapshot()["attn_probe"]["baseline"] == "exact"
