"""Pallas TPU kernel: paged-attention-native RAGGED decode, in place.

The serving engine keeps K/V in a SHARED block pool
(``num_blocks, block_size, Hkv, hd`` per layer) with a per-slot block
table.  This kernel reads that pool where it lies: the pools stay in HBM
(``memory_space=pl.ANY``) and the kernel copies each row's pages into
VMEM itself, several pages per copy group, double-buffered, with the
next group (or the next row's first group) in flight while the current
one is computed.  No dense per-row view of K/V exists anywhere.

What a row reads is what its math needs:

  - **Live blocks only.**  A row stops at the block holding its largest
    query position (``positions[b, T-1]``, a scalar-prefetch operand):
    ``live = min(pos // block_size + 1, nb)`` pages.  Padded block-table
    columns (the engine pads with the row's own first block) cost
    neither a copy nor compute.  Page slots of a copy group past the
    live extent are not copied; they hold finite stale pages from an
    earlier group (the buffers are zeroed once) and the position mask
    discards them.
  - **Real queries only.**  Padding repeats a row's last (token,
    position), so a row's real width is ``pos[b, T-1] - pos[b, 0] + 1``.
    A width-1 row (a decode row riding a wide step) computes its ``Hq``
    query rows once and writes that result to all ``T`` columns; wider
    rows (prefill chunks, draft windows) compute the whole window, and
    their padded columns are copies of the last real one, bit for bit.
    Queries that share a row's position are therefore taken to be
    copies of its last query, which is what the engine gives.

Decode is RAGGED (every row at its own position) and MULTI-TOKEN (a row
may carry ``T > 1`` queries at ascending positions: a speculative draft
window or a prefill chunk); query ``t`` masks ``kv_pos <= pos[b, t]``,
which is the causal mask inside any ascending window.

  grid = (B,)                       sequential: a row prefetches the next
  q    (1, T, Hq, hd) VMEM block    indexed (b, 0, 0, 0)
  k/v  (num_blocks, bs, Hkv, hd)    left in HBM; pages copied by hand
  out  (1, T, Hq, hd) VMEM block    written once per row

The inner loop is the online-softmax carry (m, l, acc) over copy groups
of ``pages * block_size`` keys, GQA-native: scores are computed per KV
head over its ``g = Hq // Hkv`` query group, no K/V repeat.  The matrix
products take their operands in the query's dtype (bf16 when serving)
and accumulate in f32; masks, the carry and the softmax stay in f32.
Masked lanes are -inf before they touch the carry, and a fully-masked
group leaves it untouched.

The score function is a STATIC mode (``attn_approx``, default
``'exact'``): the approximate-attention catalog (``core/attn_approx.py``)
swaps the exp sites of the online carry for exp-free datapaths —
pseudo-softmax (2^x outright), or winner-take-all ``maxonly`` (a pure
comparator carry: the output is the V row of the running max score);
``base2`` and ``pwl`` look their weights up in a table, a gather Mosaic
does not lower, so they run in interpret mode only and the compiled
kernel refuses them.  ``window`` adds a sliding-window mask
(``kv_pos > pos - window``) on top of the causal cap.

The whole query window stays resident in VMEM; ``MAX_T`` (128) is the
widest window the chip's scoped VMEM takes at qwen3-0.6b widths until
the kernel tiles T.

Validated in interpret mode against ``ref.paged_attention``.
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
from repro.parallel import env

_NEG_INF = float("-inf")
# score functions whose weight is a table lookup (base-2 fractional LUT,
# PWL endpoint ROM): interpret mode only
_LUT_MODES = ("base2", "pwl")
# the modes the compiled kernel lowers, and the widest query window it
# holds in VMEM
COMPILED_MODES = ("exact", "pseudo", "maxonly")
MAX_T = 128
# pages copied per group: 16 x 16-token pages = 256 keys, 512 KiB of
# bf16 K and V at qwen3-0.6b widths
PAGES_PER_GROUP = 16


def kernel_runs(t: int, *, use_pallas: bool = False,
                attn_approx: str = "exact", mesh=None) -> bool:
    """Whether ``ops.paged_attention`` runs this kernel for a step of
    query width ``t``: always when ``use_pallas`` asks for it (interpret
    mode off the TPU); otherwise compiled, on a TPU backend, for a mode
    it lowers, at ``t <= MAX_T``, with the pool whole on each device (no
    ``model`` axis over 1 in ``mesh``, the ambient mesh when None).
    Plain Python read at trace time; the engine's ``attn_*`` counters
    ask the same question."""
    if use_pallas:
        return True
    if mesh is None:
        mesh = env.current_mesh()
    split = mesh is not None and int(dict(mesh.shape).get("model", 1)) > 1
    return (jax.default_backend() == "tpu" and attn_approx in COMPILED_MODES
            and t <= MAX_T and not split)


def live_blocks(last_pos, block_size: int, nb: int):
    """Pages a row reads: through the block holding its largest query
    position, at least one and never past its table.  Takes a traced
    scalar in the kernel and a host array in the engine's counters."""
    return (last_pos // block_size + 1).clip(1, nb)


def _kernel(btab_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, slot_ref, qg_ref, thr_ref, m_ref, l_ref,
            acc_ref, *,
            scale: float, block_size: int, nb: int, pages: int, t: int,
            g: int, variant: str, window: Optional[int]):
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    hkv, hd = kbuf.shape[3], kbuf.shape[4]
    keys = pages * block_size

    def n_live(r):
        return live_blocks(pos_ref[r * t + t - 1], block_size, nb)

    def copies(r, grp, slot, method):
        """Start (or wait for) the copies of row ``r``'s live pages in
        its group ``grp``, K and V into buffer ``slot``."""
        first = grp * pages

        def page(i, carry):
            blk = btab_ref[r * nb + first + i]
            for pool, buf, j in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                getattr(pltpu.make_async_copy(
                    pool.at[blk], buf.at[slot, i], sems.at[j, slot]),
                    method)()
            return carry

        jax.lax.fori_loop(0, jnp.minimum(pages, n_live(r) - first), page, 0)

    def start(r, grp, slot):
        copies(r, grp, slot, "start")

    def wait(r, grp, slot):
        copies(r, grp, slot, "wait")

    @pl.when(b == 0)
    def _first():
        # slots a group does not fill keep what they held: make that
        # finite once (masked scores meet V only through a zero weight)
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        slot_ref[0] = 0
        start(0, 0, 0)

    s0 = slot_ref[0]
    n_groups = (n_live(b) + pages - 1) // pages
    last = pos_ref[b * t + t - 1]
    width = last - pos_ref[b * t] + 1

    def prepare(tq):
        """Row ``b``'s last ``tq`` queries (1: a decode row; t: the whole
        window) as (Hkv, tq*g, hd), each KV head with its query group,
        their positions, and a fresh carry."""
        r = tq * g
        q = q_ref[0, t - tq:].astype(jnp.float32)
        qg_ref[:, :r] = q.reshape(tq, hkv, g, hd).transpose(
            1, 0, 2, 3).reshape(hkv, r, hd).astype(qg_ref.dtype)
        # query row i is query i // g of the window
        row = jax.lax.broadcasted_iota(jnp.int32, (1, r, 1), 1)
        thr_ref[:, :r] = jax.lax.fori_loop(
            1, tq, lambda j, thr: jnp.where(
                row >= j * g, pos_ref[b * t + t - tq + j], thr),
            jnp.full((1, r, 1), pos_ref[b * t + t - tq], jnp.int32))
        m_ref[:, :r] = jnp.full((hkv, r, 1), _NEG_INF, jnp.float32)
        l_ref[:, :r] = jnp.zeros((hkv, r, 1), jnp.float32)
        acc_ref[:, :r] = jnp.zeros((hkv, r, hd), jnp.float32)

    def update(tq, grp, slot):
        """One online-carry step of the prepared queries over the group
        in buffer ``slot``."""
        r = tq * g
        # (keys, Hkv, hd) -> (Hkv, keys, hd), in f32 for the relayout
        kt, vt = (buf[slot].astype(jnp.float32).reshape(keys, hkv, hd)
                  .transpose(1, 0, 2).astype(qg_ref.dtype)
                  for buf in (kbuf, vbuf))
        thr = thr_ref[:, :r]
        s = jax.lax.dot_general(
            qg_ref[:, :r], kt, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale      # (Hkv, r, K)
        kv_pos = grp * keys + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        valid = kv_pos <= thr
        if window is not None:
            valid &= kv_pos > thr - window
        s = jnp.where(valid, s, _NEG_INF)

        m_prev, l_prev = m_ref[:, :r], l_ref[:, :r]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        if variant == "maxonly":
            # winner-take-all carry: when this group holds a STRICTLY
            # higher score than the carry so far, reset the accumulator to
            # the (first) winner's V row; exact ties keep the earlier
            # (lowest-position) winner.  A fully-masked group has m_cur =
            # -inf and touches nothing.
            iota = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            hit = valid & (s == m_cur)
            first = jnp.min(jnp.where(hit, iota, jnp.iinfo(jnp.int32).max),
                            axis=-1, keepdims=True)
            take = m_cur > m_prev
            p = jnp.where(take & (iota == first), 1.0, 0.0)
            alpha = jnp.where(take, 0.0, 1.0)
        else:
            # rows with no valid key yet keep m = -inf: guard f(-inf -
            # -inf).  The weight is evaluated once per score at this
            # group's running max and the carry rescaled in the variant's
            # base, so an approximate f stays single-shot per score.
            safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            d = jnp.where(valid, s - safe_m, 0.0)
            p = jnp.where(valid, approx.weight_exp(d, variant), 0.0)
            alpha = jnp.where(jnp.isfinite(m_prev),
                              approx.carry_scale(m_prev - safe_m, variant),
                              0.0)
        l_ref[:, :r] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(vt.dtype), vt, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)               # (Hkv, r, hd)
        acc_ref[:, :r] = acc_ref[:, :r] * alpha + pv
        m_ref[:, :r] = m_new

    def finish(tq):
        """The carry's result as (tq, Hq, hd)."""
        r = tq * g
        out = acc_ref[:, :r] / jnp.maximum(l_ref[:, :r], 1e-30)
        return out.reshape(hkv, tq, g, hd).transpose(1, 0, 2, 3).reshape(
            tq, hkv * g, hd).astype(o_ref.dtype)

    def by_width(fn):
        """``fn(1)`` for a decode row (its one real query), ``fn(t)`` for
        any other row (its whole window)."""
        if t == 1:
            return fn(1)
        pl.when(width == 1)(lambda: fn(1))
        pl.when(width > 1)(lambda: fn(t))

    by_width(prepare)

    def group(grp, carry):
        slot = (s0 + grp) % 2

        @pl.when(grp + 1 < n_groups)
        def _next_group():
            start(b, grp + 1, 1 - slot)

        @pl.when((grp + 1 == n_groups) & (b + 1 < rows))
        def _next_row():
            start(b + 1, 0, 1 - slot)

        wait(b, grp, slot)
        by_width(lambda tq: update(tq, grp, slot))
        return carry

    jax.lax.fori_loop(0, n_groups, group, 0)

    def emit(tq):
        if tq == 1:
            o_ref[0] = jnp.broadcast_to(finish(1), o_ref.shape[1:])
            return
        o_ref[0] = finish(tq)
        # padded columns: copies of the last real query, bit for bit
        real = jnp.minimum(width, t)

        def pad(j, carry):
            o_ref[0, j] = o_ref[0, real - 1]
            return carry

        jax.lax.fori_loop(real, t, pad, 0)

    by_width(emit)
    slot_ref[0] = (s0 + n_groups) % 2


@functools.partial(jax.jit,
                   static_argnames=("interpret", "attn_approx", "window"))
def paged_attention(q, k_pool, v_pool, block_tables, positions, *,
                    interpret: bool = False, attn_approx: str = "exact",
                    window: Optional[int] = None):
    """q: (B, Hq, hd) — or (B, T, Hq, hd) for a multi-token step;
    k/v_pool: (num_blocks, bs, Hkv, hd); block_tables: (B, nb) int32;
    positions: (B,) int32 — (B, T) in the multi-token form, ascending
    within a row, padding repeating the row's last position — each
    query attends over its OWN kv positions <= its position (a scalar
    broadcasts to the whole batch).  ``attn_approx`` picks the score
    function from the ``core.attn_approx`` catalog; ``window`` caps
    each query to its last ``window`` kv positions.  Both are static
    (per-mode compilation).  -> (B, Hq, hd) / (B, T, Hq, hd)."""
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
    pages = min(PAGES_PER_GROUP, nb)
    positions = jnp.broadcast_to(
        jnp.asarray(positions, jnp.int32).reshape(
            (-1, t) if jnp.ndim(positions) == 2 else (-1, 1)), (b, t))

    kern = functools.partial(
        _kernel, scale=1.0 / math.sqrt(hd), block_size=bs, nb=nb,
        pages=pages, t=t, g=g, variant=attn_approx, window=window)
    blk = pl.BlockSpec((1, t, hq, hd), lambda bi, bt, pp: (bi, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[blk, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=blk,
        scratch_shapes=[
            pltpu.VMEM((2, pages, bs, hkv, hd), k_pool.dtype),
            pltpu.VMEM((2, pages, bs, hkv, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((hkv, t * g, hd), q.dtype),
            pltpu.VMEM((1, t * g, 1), jnp.int32),
            pltpu.VMEM((hkv, t * g, 1), jnp.float32),
            pltpu.VMEM((hkv, t * g, 1), jnp.float32),
            pltpu.VMEM((hkv, t * g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, t, hq, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_tables.astype(jnp.int32).reshape(-1), positions.reshape(-1),
      q, k_pool, v_pool)
    return out if multi else out[:, 0]
