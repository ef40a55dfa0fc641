"""Plain float32 reference of a Qwen3 decoder, and its lower-precision
control.

Follows the published Qwen3 description (Hugging Face ``Qwen3ForCausalLM``):
token embedding; per layer ``x += o_proj(attn(rms(x)))`` with per-head
RMSNorm on q and k before rotary embedding (rotate-half form, base
``rope_theta``), grouped-query causal softmax attention scaled by
``head_dim ** -0.5``; ``x += down(silu(gate(h)) * up(h))`` on
``h = rms(x)``; a final RMSNorm and the (tied or untied) vocabulary
projection.  RMSNorm gains are read as ``1 + stored`` (see
``weights.py``).  No cache, no kernels, no serving head: one full
forward over a whole sequence, in float32 under ``highest`` matmul
precision, layer by layer and in blocks of rows so that it fits beside
nothing else on the chip.

``quant="fp8"`` is the control: the same forward with both operands of
every matrix product rounded to float8 e4m3 (weights per output
channel, activations per row), the precision below the bfloat16 that
the configuration states.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ROW_BLOCK = 512        # rows of the vocabulary projection per block
Q_BLOCK = 1024         # queries per attention block


def _fp8(x, axis):
    """Round to float8 e4m3 with an absmax scale along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(x, w, quant):
    """x (..., K) @ w (K, N) in float32; the control rounds both to fp8."""
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.dot(x, w)


def _rms(x, gain, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + gain.astype(F32))


def _rope(x, theta):
    """x (S, H, hd), position i at row i (rotate-half form)."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal grouped-query attention; q (S, Hq, hd), k/v (S, Hkv, hd)."""
    S, hq, hd = q.shape
    g = hq // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    nq = -(-S // Q_BLOCK)
    qp = jnp.pad(q, ((0, nq * Q_BLOCK - S), (0, 0), (0, 0)))

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qp, i * Q_BLOCK, Q_BLOCK, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * (hd ** -0.5)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(qpos[None, :, None] >= jnp.arange(S)[None, None, :],
                      s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(block, jnp.arange(nq))
    return out.reshape(nq * Q_BLOCK, hq, hd)[:S]


def hidden(w, dims: dict, tokens, quant=None):
    """Final normed hidden states (S, D) of ``tokens`` (S,)."""
    eps, theta = dims["norm_eps"], dims["rope_theta"]
    hq, hkv, hd = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    S = tokens.shape[0]
    x = w["embed"][tokens].astype(F32)
    stack = w["decoder"][0]["slot0"]

    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(F32), p)
        a = p["attn"]
        h = _rms(x, p["ln1"], eps)
        q = _mm(h, a["wq"], quant).reshape(S, hq, hd)
        k = _mm(h, a["wk"], quant).reshape(S, hkv, hd)
        v = _mm(h, a["wv"], quant).reshape(S, hkv, hd)
        q = _rope(_rms(q, a["q_norm"], eps), theta)
        k = _rope(_rms(k, a["k_norm"], eps), theta)
        o = _attention(q, k, v).reshape(S, hq * hd)
        x = x + _mm(o, a["wo"], quant)
        m = p["mlp"]
        h = _rms(x, p["ln2"], eps)
        x = x + _mm(jax.nn.silu(_mm(h, m["w_gate"], quant))
                    * _mm(h, m["w_up"], quant), m["w_out"], quant)
        return x, None

    x, _ = jax.lax.scan(layer, x, stack)
    return _rms(x, w["final_norm"], eps)


def _head(w):
    return w["embed"].T if "lm_head" not in w else w["lm_head"]


def _blocks(h):
    n = -(-h.shape[0] // ROW_BLOCK)
    return jnp.pad(h, ((0, n * ROW_BLOCK - h.shape[0]), (0, 0))).reshape(
        n, ROW_BLOCK, -1)


@functools.partial(jax.jit, static_argnames=("dims",))
def _served_gaps(w, dims, tokens, served):
    """Per position: reference best logit minus the reference logit of
    ``served`` (the token the program put there)."""
    with jax.default_matmul_precision("highest"):
        h = hidden(w, dict(dims), tokens)
        head = _head(w)

        def blk(args):
            hb, sb = args
            lg = _mm(hb, head.astype(F32), None)
            return lg.max(-1) - jnp.take_along_axis(lg, sb[:, None], 1)[:, 0]

        n = -(-served.shape[0] // ROW_BLOCK)
        sv = jnp.pad(served, (0, n * ROW_BLOCK - served.shape[0]))
        return jax.lax.map(blk, (_blocks(h), sv.reshape(n, ROW_BLOCK))
                           ).reshape(-1)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _control_gaps(w, dims, tokens, quant):
    """Per position: reference best logit minus the reference logit of
    the token the lower-precision forward puts first."""
    with jax.default_matmul_precision("highest"):
        d = dict(dims)
        h = hidden(w, d, tokens)
        hc = hidden(w, d, tokens, quant=quant)
        head = _head(w).astype(F32)

        def blk(args):
            hb, cb = args
            lg = _mm(hb, head, None)
            pick = jnp.argmax(_mm(cb, head, quant), -1)
            return lg.max(-1) - jnp.take_along_axis(lg, pick[:, None], 1)[:, 0]

        return jax.lax.map(blk, (_blocks(h), _blocks(hc))).reshape(-1)


BUCKETS = (512, 1024, 1536, 2048, 3072, 4096)


def bucket(n: int) -> int:
    """Pad lengths to one of a few sizes so few programs compile."""
    for b in BUCKETS:
        if n <= b:
            return b
    return -(-n // BUCKETS[-1]) * BUCKETS[-1]


def _frozen(dims: dict):
    return tuple(sorted(dims.items()))


def _padded(prompt, generated):
    """``prompt + generated[:-1]`` padded to its bucket, and the index of
    the position that predicts served token 0.  Padding at the end
    cannot reach earlier positions under the causal mask."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(generated[:-1], np.int32)])
    toks = np.zeros(bucket(len(seq)), np.int32)
    toks[:len(seq)] = seq
    return toks, len(prompt) - 1


def served_gaps(w, dims: dict, prompt, generated):
    """Gap of each served token of one request (len(generated),):
    position ``len(prompt) - 1 + t`` predicts served token ``t``."""
    toks, first = _padded(prompt, generated)
    n = len(generated)
    served = np.zeros(len(toks), np.int32)
    served[first:first + n] = generated
    gaps = _served_gaps(w, _frozen(dims), jnp.asarray(toks),
                        jnp.asarray(served))
    return np.asarray(gaps)[first:first + n]


def control_gaps(w, dims: dict, prompt, generated, quant="fp8"):
    """The control's gaps at the same positions as ``served_gaps``."""
    toks, first = _padded(prompt, generated)
    gaps = _control_gaps(w, _frozen(dims), jnp.asarray(toks), quant)
    return np.asarray(gaps)[first:first + len(generated)]
