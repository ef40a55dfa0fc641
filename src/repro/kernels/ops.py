"""Public, jit'd entry points for the kernels package.

Every op takes the same ``use_pallas``/``interpret`` switches, resolved
in ONE place (``resolve_flags``):

  - ``use_pallas=False`` (default) -> the pure-jnp oracle (ref.py). This
    is what the dry-run lowers, so roofline numbers are XLA's, not the
    interpreter's.  One exception: ``paged_attention`` runs its compiled
    kernel on a TPU backend wherever that kernel applies
    (``paged_attention.kernel_runs``): the oracle's dense gather is what
    the serving step cannot afford there.
  - ``use_pallas=True, interpret=None`` -> auto: the real VMEM-tiled
    kernel on TPU, Pallas interpret mode everywhere else (CPU CI).
  - explicit ``interpret=True/False`` is honored as given (tests pin
    interpret mode; TPU runs pin compiled mode).

Historically each entry hardcoded ``interpret=True`` while defaulting
``use_pallas=False`` — a dead flag on the ref path and a silent
interpreter fallback on TPU for callers who flipped ``use_pallas`` only.
``resolve_flags`` is the single source of truth; ``fused_*``,
``online_softmax``, ``softmax_*`` and ``paged_attention`` all share it.

``softmax_xent`` is differentiable (custom_vjp): forward avoids
materializing probabilities; backward recomputes ``softmax - onehot``
blockwise from the saved logits instead of storing probs as residuals.

LOOP SAFETY: every entry here dispatches at TRACE TIME only — flag
resolution (``resolve_flags``, including the ``jax.default_backend()``
probe) is plain Python executed while tracing, and no op ever calls
back to the host (no ``io_callback``/``pure_callback``/``debug`` sync).
Each op is therefore closed under ``lax.while_loop``/``lax.scan``
bodies: the device-resident multi-step decode loop
(``api.serve_decode_multi``) traces the paged-attention kernel and the
comparator heads straight into its loop body and runs K iterations
with zero host involvement.  Keep it that way — a host callback inside
any of these ops would silently serialize the decode loop.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import attn_approx as attn_approx_mod
from repro.kernels import fused_argmax_head as _fah
from repro.kernels import fused_topk_head as _ftk
from repro.kernels import fused_xent as _fx
from repro.kernels import online_softmax as _os
from repro.kernels import paged_attention as _pa
from repro.kernels import ref


def resolve_flags(use_pallas: bool, interpret: Optional[bool]):
    """Normalize the (use_pallas, interpret) pair for every kernel entry.

    ``interpret=None`` means auto: interpret everywhere except a real
    TPU backend.  Explicit True/False passes through untouched.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return bool(use_pallas), bool(interpret)


def fused_argmax_head(h, w, *, use_pallas: bool = False,
                      interpret: Optional[bool] = None, **block_kw):
    """argmax_v(h @ w) -> (B,) int32. The paper's reduced unit, fused."""
    use_pallas, interpret = resolve_flags(use_pallas, interpret)
    if use_pallas:
        return _fah.fused_argmax_head(h, w, interpret=interpret, **block_kw)
    return ref.fused_argmax_head(h, w)


def fused_argmax_head_with_value(h, w, *, use_pallas: bool = False,
                                 interpret: Optional[bool] = None,
                                 **block_kw):
    use_pallas, interpret = resolve_flags(use_pallas, interpret)
    if use_pallas:
        return _fah.fused_argmax_head_with_value(
            h, w, interpret=interpret, **block_kw)
    return ref.fused_argmax_head_with_value(h, w)


def fused_topk_head(h, w, k, *, use_pallas: bool = False,
                    interpret: Optional[bool] = None, **block_kw):
    """Top-k (vals, idxs) of h @ w — the reduced unit's k-winner form."""
    use_pallas, interpret = resolve_flags(use_pallas, interpret)
    if use_pallas:
        return _ftk.fused_topk_head(h, w, k, interpret=interpret, **block_kw)
    return ref.fused_topk_head(h, w, k)


def verify_draft(h, w, cand, *, use_pallas: bool = False,
                 interpret: Optional[bool] = None):
    """Speculative-decoding verification — the comparator-only unit.

    h (B, T, D) hidden states at T consecutive positions; w (D, V);
    cand (B, T-1) int32 draft ids (-1-padded past a row's real width).
    Returns (ids (B, T) i32, accept (B,) i32): the per-position greedy
    argmax via the reduced comparator and the length of the accepted
    draft prefix — greedy emits exactly ``ids[b, :accept[b]+1]`` this
    step.  Zero exp / zero sum / zero divide (Theorem 1 at K+1
    positions); the Pallas path never materializes the logits.
    """
    use_pallas, interpret = resolve_flags(use_pallas, interpret)
    if use_pallas:
        return _ftk.fused_verify_head(h, w, cand, interpret=interpret)
    return ref.verify_draft(h, w, cand)


def paged_attention(q, k_pool, v_pool, block_tables, positions, *,
                    use_pallas: bool = False,
                    interpret: Optional[bool] = None,
                    attn_approx: str = "exact",
                    window: Optional[int] = None):
    """Ragged decode attention straight off a block-paged KV pool.

    q (B, Hq, hd) — or (B, T, Hq, hd) for a MULTI-TOKEN step (draft
    window or prefill chunk); pools (num_blocks, block_size, Hkv, hd);
    block_tables (B, nb) i32; positions (B,) i32 — or (B, T) i32
    per-query positions in the multi-token form, ascending within a row
    and padded by repeating the row's last position — each query attends
    over its own kv positions <= its position (a scalar broadcasts)
    -> (B, Hq, hd) / (B, T, Hq, hd).

    Dispatch (``paged_attention.kernel_runs``): the Pallas kernel reads
    each row's live pool blocks in place and computes a decode row's one
    real query once; it runs when ``use_pallas`` asks (interpret mode
    off the TPU), and, with ``use_pallas=False``, compiled on a TPU
    backend whenever the mode compiles (exact, pseudo, maxonly), T <=
    128 and the pool is not split across devices (tp = 1).  Everything
    else — the CPU, tp > 1 pools, T > 128, base2/pwl — runs
    ``ref.paged_attention``, the dense decode math over the gathered
    view, token-exact against the dense cache layout.

    ``attn_approx`` picks the score function from the
    ``core.attn_approx`` catalog ('exact' | 'base2' | 'pseudo' | 'pwl' |
    'maxonly'); ``window`` caps each query to its last ``window`` kv
    positions.  Both are STATIC modes resolved here at trace time
    (loop-safe, like the flag pair) and honored identically by both
    twins.
    """
    use_pallas, interpret = resolve_flags(use_pallas, interpret)
    attn_approx, window = attn_approx_mod.resolve(attn_approx, window)
    t = q.shape[1] if q.ndim == 4 else 1
    if _pa.kernel_runs(t, use_pallas=use_pallas, attn_approx=attn_approx):
        return _pa.paged_attention(q, k_pool, v_pool, block_tables,
                                   positions,
                                   interpret=use_pallas and interpret,
                                   attn_approx=attn_approx, window=window)
    return ref.paged_attention(q, k_pool, v_pool, block_tables, positions,
                               attn_approx=attn_approx, window=window)


def online_softmax(x, *, use_pallas: bool = False,
                   interpret: Optional[bool] = None, **block_kw):
    """The full softmax unit (baseline)."""
    use_pallas, interpret = resolve_flags(use_pallas, interpret)
    if use_pallas:
        return _os.online_softmax(x, interpret=interpret, **block_kw)
    return ref.online_softmax(x)


def softmax_stats(x, *, use_pallas: bool = False,
                  interpret: Optional[bool] = None, **block_kw):
    use_pallas, interpret = resolve_flags(use_pallas, interpret)
    if use_pallas:
        return _os.softmax_stats(x, interpret=interpret, **block_kw)
    return ref.softmax_stats(x)


# ---------------------------------------------------------------------------
# Differentiable fused cross-entropy
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def softmax_xent(logits, labels, use_pallas: bool = False,
                 interpret: Optional[bool] = None):
    """Per-row softmax CE, probs never materialized in the forward."""
    use_pallas, interpret = resolve_flags(use_pallas, interpret)
    if use_pallas:
        return _fx.fused_xent(logits, labels, interpret=interpret)
    return ref.fused_xent(logits, labels)


def _xent_fwd(logits, labels, use_pallas, interpret):
    loss = softmax_xent(logits, labels, use_pallas, interpret)
    return loss, (logits, labels)


def _xent_bwd(use_pallas, interpret, res, g):
    logits, labels = res
    # Recompute softmax from logits (no prob residuals).
    p = ref.online_softmax(logits)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=p.dtype)
    dlogits = (p - onehot) * g[:, None]
    return dlogits.astype(logits.dtype), None


softmax_xent.defvjp(_xent_fwd, _xent_bwd)
