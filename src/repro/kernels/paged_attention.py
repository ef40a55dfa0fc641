"""Pallas TPU kernel: paged-attention-native RAGGED decode.

The serving engine keeps K/V in a SHARED block pool
(``num_blocks, block_size, Hkv, hd`` per layer) with a per-slot block
table.  The seed engine gathered that pool into a dense ``(B, S, ...)``
cache before every decode step — an O(seq_len) copy and re-layout per
token that doubles HBM traffic over what attention itself must read.
This kernel deletes the copy: the grid walks ``(batch row, block)`` and
the BLOCK TABLE itself drives the BlockSpec index maps (scalar
prefetch), so each pool block is DMA'd HBM->VMEM exactly once, in
place, and the dense view never exists anywhere.

Decode is RAGGED: every batch row sits at its OWN position (the engine
fuses all active slots into one step regardless of where each sequence
is), so ``positions`` is a per-row scalar-prefetch vector and the
valid-key mask is per row: ``kv_pos <= positions[b]``.

Decode is also MULTI-TOKEN: a row may carry ``T > 1`` query tokens,
each at its own position — a speculative draft window (last committed
token plus K drafts, verified in ONE forward) or a PREFILL CHUNK of
consecutive prompt positions (chunked admission: the engine scatters
the chunk's K/V into the row's pool blocks and serves it beside the
decode rows in the same call).  ``q`` grows a T axis and ``positions``
becomes a per-(row, query) ``(B, T)`` matrix; query ``t`` masks
``kv_pos <= positions[b, t]``, which IS the causal mask inside any
ascending window — draft or chunk — while padding queries that repeat
their row's last (token, position) reproduce its output exactly, so
mixed widths share one compiled call.

  grid = (B, nb)                      # nb = max blocks over the batch
  q     (1, T, Hq, hd)  indexed (b, 0, 0, 0)
  k/v   (1, bs, Hkv, hd) indexed (btab[b, j], 0, 0, 0)   <- the trick
  out   (1, T, Hq, hd)  written at j == nb - 1

The whole query window stays resident in VMEM: the q and out blocks,
the ``(T * Hq, hd)`` f32 accumulator and its max/sum columns.  At
qwen3-0.6b widths (Hq 16, hd 128, 16-row blocks) that compiles for a
TPU v5e up to T = 128; T = 256 asks for 18.2 MB of scoped VMEM, over the
16 MB limit, so chunk and draft windows stay at or below 128 until the
kernel tiles T.

Inner loop is the standard online-softmax carry (same (m, l, acc)
recurrence as kernels/flash_attention.py) over ``T * Hq`` query rows,
GQA-native: scores are computed per KV head over its ``g = Hq // Hkv``
query group, no K/V repeat.  Positions beyond a query's own
``positions[b, t]`` (the tail of the row's last block, whole blocks past
a short row's extent, and any padded block-table columns) are masked to
-inf before they touch the carry, so ragged rows and arbitrary pow-2
padded tables are safe — a fully-masked block leaves the carry
untouched.

The score function is a STATIC mode (``attn_approx``, default
``'exact'``): the approximate-attention catalog
(``core/attn_approx.py``) swaps the exp sites of the online carry for
exp-free hardware datapaths — base-2 shift+LUT, pseudo-softmax (2^x
outright), piecewise-linear exp, or winner-take-all ``maxonly`` (a pure
comparator carry: the output is the V row of the running max score).
``window`` adds a sliding-window mask (``kv_pos > positions - window``)
on top of the causal cap, so ``maxonly`` + ``window`` is the paper's
comparator over a sliding bus.  Both knobs branch at TRACE time —
``attn_approx='exact'``/``window=None`` traces the exact same graph as
before they existed.  ``base2`` and ``pwl`` look their weights up in a
table, a gather Mosaic does not lower, so they run in interpret mode
only; the compiled kernel refuses them.

Validated in interpret mode against ``ref.paged_attention`` (which is
itself the dense decode math applied to the gathered view).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import attn_approx as approx

_NEG_INF = float("-inf")
# score functions whose weight is a table lookup (base-2 fractional LUT,
# PWL endpoint ROM): interpret mode only
_LUT_MODES = ("base2", "pwl")


def _kernel(btab_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale: float, block_size: int,
            nb: int, g: int, variant: str, window: Optional[int]):
    bi = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                  # (T, Hq, hd)
    k = k_ref[0].astype(jnp.float32)                  # (bs, Hkv, hd)
    v = v_ref[0].astype(jnp.float32)
    tq, hq, hd = q.shape
    hkv = k.shape[1]

    # GQA scores without K repeat: batch the contraction over KV heads,
    # with the T query tokens riding inside each head group.
    qg = q.reshape(tq, hkv, g, hd).transpose(1, 0, 2, 3)   # (Hkv, T, g, hd)
    kt = k.transpose(1, 0, 2)                              # (Hkv, bs, hd)
    s = jax.lax.dot_general(
        qg.reshape(hkv, tq * g, hd), kt, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale        # (Hkv, T*g, bs)
    s = s.reshape(hkv, tq, g, -1).transpose(1, 0, 2, 3)
    s = s.reshape(tq * hq, -1)                             # (T*Hq, bs)

    # each query's own position: kv entries past it (later drafts, other
    # rows' longer extents, padded table columns) are masked out
    # entirely, so ragged batches and draft windows share one grid.
    # Score row r belongs to query token r // Hq.  The threshold column
    # is built from a 2-D row iota and one select per token: Mosaic
    # refuses the (T, Hq) -> (T*Hq, 1) shape cast a stack + repeat needs.
    row = jax.lax.broadcasted_iota(jnp.int32, (tq * hq, 1), 0)
    thr = jnp.full((tq * hq, 1), pos_ref[bi, 0], jnp.int32)
    for t in range(1, tq):
        thr = jnp.where(row >= t * hq, pos_ref[bi, t], thr)  # (T*Hq, 1)
    kv_pos = j * block_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = kv_pos <= thr
    if window is not None:
        # sliding-window cap: only the last `window` positions (own
        # position included) stay visible — same convention as
        # ref.flash_attention's k_idx > q_idx - window
        valid &= kv_pos > thr - window
    s = jnp.where(valid, s, _NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)         # (T*Hq, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    if variant == "maxonly":
        # winner-take-all carry: no weights at all — when this block
        # holds a STRICTLY higher score than the carry so far, reset the
        # accumulator to the (first) winner's V row; exact ties keep the
        # earlier (lowest-position) winner, matching argmax semantics.
        # A fully-masked block has m_cur = -inf and touches nothing.
        iota = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        hit = valid & (s == m_cur)
        first = jnp.min(jnp.where(hit, iota, jnp.iinfo(jnp.int32).max),
                        axis=-1, keepdims=True)
        take = m_cur > m_prev
        p = jnp.where(take & (iota == first), 1.0, 0.0)
        alpha = jnp.where(take, 0.0, 1.0)
    elif variant == "exact":
        # rows with no valid key yet keep m = -inf; guard exp(-inf - -inf)
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(jnp.where(valid, s - safe_m, _NEG_INF))
        alpha = jnp.where(jnp.isfinite(m_prev),
                          jnp.exp(m_prev - safe_m), 0.0)
    else:
        # approximate weight, exact rescale: f is evaluated once per
        # score at this block's running max and the carry is rescaled in
        # the variant's base (attn_approx.carry_scale), so the LUT/PWL
        # error stays single-shot per score instead of compounding per
        # block — paged matches ref's global-max definition tightly.
        # The LUT f's are undefined at -inf: masked lanes are zeroed
        # explicitly instead of riding exp(-inf) = 0.
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        d = jnp.where(valid, s - safe_m, 0.0)
        p = jnp.where(valid, approx.weight_exp(d, variant), 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev),
                          approx.carry_scale(m_prev - safe_m, variant), 0.0)
    l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pg = p.reshape(tq, hkv, g, -1).transpose(1, 0, 2, 3)
    pv = jax.lax.dot_general(
        pg.reshape(hkv, tq * g, -1), v.transpose(1, 0, 2),
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)            # (Hkv, T*g, hd)
    pv = pv.reshape(hkv, tq, g, hd).transpose(1, 0, 2, 3)
    acc_ref[...] = acc_ref[...] * alpha + pv.reshape(tq * hq, hd)
    m_ref[...] = m_new

    @pl.when(j == nb - 1)
    def _emit():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).reshape(tq, hq, hd).astype(
            o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "attn_approx", "window"))
def paged_attention(q, k_pool, v_pool, block_tables, positions, *,
                    interpret: bool = False, attn_approx: str = "exact",
                    window: Optional[int] = None):
    """q: (B, Hq, hd) — or (B, T, Hq, hd) for a multi-token
    (speculative) step; k/v_pool: (num_blocks, bs, Hkv, hd);
    block_tables: (B, nb) int32; positions: (B,) int32 — (B, T) in the
    multi-token form — each query attends over its OWN kv positions <=
    its position (a scalar broadcasts to the whole batch).
    ``attn_approx`` picks the score function from the
    ``core.attn_approx`` catalog; ``window`` caps each query to its last
    ``window`` kv positions.  Both are static (per-mode compilation).
    -> (B, Hq, hd) / (B, T, Hq, hd)."""
    if not interpret and attn_approx in _LUT_MODES:
        raise NotImplementedError(
            f"attn_approx={attn_approx!r} has no compiled TPU kernel: its "
            "table lookup is a gather Mosaic cannot lower (it lowers only "
            "2-D gathers).  Run it with use_pallas=False (the jnp "
            "reference) or interpret=True.")
    multi = q.ndim == 4
    if not multi:
        q = q[:, None]
    b, t, hq, hd = q.shape
    bs, hkv = k_pool.shape[1], k_pool.shape[2]
    nb = block_tables.shape[1]
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    scale = 1.0 / math.sqrt(hd)
    positions = jnp.broadcast_to(
        jnp.asarray(positions, jnp.int32).reshape(
            (-1, t) if jnp.ndim(positions) == 2 else (-1, 1)), (b, t))

    kern = functools.partial(_kernel, scale=scale, block_size=bs,
                             nb=nb, g=g, variant=attn_approx,
                             window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((1, t, hq, hd),
                         lambda bi, ji, bt, pp: (bi, 0, 0, 0)),
            pl.BlockSpec((1, bs, hkv, hd),
                         lambda bi, ji, bt, pp: (bt[bi, ji], 0, 0, 0)),
            pl.BlockSpec((1, bs, hkv, hd),
                         lambda bi, ji, bt, pp: (bt[bi, ji], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, t, hq, hd),
                               lambda bi, ji, bt, pp: (bi, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((t * hq, 1), jnp.float32),
            pltpu.VMEM((t * hq, 1), jnp.float32),
            pltpu.VMEM((t * hq, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, t, hq, hd), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), positions, q, k_pool, v_pool)
    return out if multi else out[:, 0]
