"""Shared neural-net layers for the model zoo.

Everything is a pure function ``f(params, x, ...) -> y`` over plain dict
pytrees, so stacks can be ``lax.scan``-ed with stacked params and sharded
with pjit. Compute dtype is the config dtype (bf16 by default); norms and
softmax run in f32.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoESpec


def cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x, scale, bias, eps=1e-6):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def group_norm_heads(x: jax.Array, scale: jax.Array, n_heads: int,
                     eps: float = 64e-5) -> jax.Array:
    """Per-head group norm (RWKV's ln_x). x: (..., H*hd)."""
    shp = x.shape
    xf = x.astype(jnp.float32).reshape(*shp[:-1], n_heads, -1)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y.reshape(shp) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, T, H, hd); positions: (B, T) or (T,) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                      # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., T, hd/2)
    if ang.ndim == 2:  # (T, hd/2) -> broadcast over batch
        ang = ang[None]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------
def activate(h_gate: jax.Array, h_up: Optional[jax.Array], kind: str):
    if kind == "silu_glu":
        return jax.nn.silu(h_gate) * h_up
    if kind == "gelu_glu":
        return jax.nn.gelu(h_gate) * h_up
    if kind == "gelu":
        return jax.nn.gelu(h_gate)
    if kind == "relu":
        return jax.nn.relu(h_gate)
    if kind == "squared_relu":
        r = jax.nn.relu(h_gate)
        return r * r
    if kind == "relu_sq":
        r = jax.nn.relu(h_gate)
        return r * r
    raise ValueError(kind)


def is_glu(kind: str) -> bool:
    return kind.endswith("_glu")


# ---------------------------------------------------------------------------
# Dense MLP
# ---------------------------------------------------------------------------
def mlp(p, x: jax.Array, kind: str) -> jax.Array:
    with jax.named_scope("mlp"):
        if is_glu(kind):
            h = activate(x @ p["w_gate"], x @ p["w_up"], kind)
        else:
            h = activate(x @ p["w_in"], None, kind)
        return h @ p["w_out"]


def init_mlp(key, d: int, f: int, kind: str, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(f)
    if is_glu(kind):
        return {
            "w_gate": jax.random.normal(k1, (d, f), dtype) * s_in,
            "w_up": jax.random.normal(k2, (d, f), dtype) * s_in,
            "w_out": jax.random.normal(k3, (f, d), dtype) * s_out,
        }
    return {
        "w_in": jax.random.normal(k1, (d, f), dtype) * s_in,
        "w_out": jax.random.normal(k3, (f, d), dtype) * s_out,
    }


# ---------------------------------------------------------------------------
# Attention (GQA + qk-norm + RoPE + sliding window + KV cache)
# ---------------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig, cross: bool = False):
    d, qw, kw = cfg.d_model, cfg.q_width, cfg.kv_width
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    p = {
        "wq": jax.random.normal(ks[0], (d, qw)) * s,
        "wk": jax.random.normal(ks[1], (d, kw)) * s,
        "wv": jax.random.normal(ks[2], (d, kw)) * s,
        "wo": jax.random.normal(ks[3], (qw, d)) / math.sqrt(qw),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.zeros((cfg.head_dim,))
        p["k_norm"] = jnp.zeros((cfg.head_dim,))
    return p


def _split_heads(x, n_heads, head_dim):
    return x.reshape(*x.shape[:-1], n_heads, head_dim)


# Probe hook (repro.probe): when set to a list, the paged-attention
# branch appends its concrete (q, ck, cv, block_tables, cpm) operands
# per layer per call.  Only meaningful under jax.disable_jit() — inside
# a jit trace the values are tracers and the append is a trace-time
# side effect.  Leave None in production paths.
_ATTN_TAP: Optional[list] = None


def attention(
    p,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,            # (T,) or (B, T)
    kv_x: Optional[jax.Array] = None,   # cross-attention source
    causal: bool = True,
    window: Optional[int] = None,
    cache: Optional[dict] = None,       # {'k','v'}: (B, S_cache, Hkv, hd)
    cache_pos: Optional[jax.Array] = None,  # int32 write index base:
                                            # scalar, (B,) per-row (ragged
                                            # decode), or (B, T) per-token
                                            # (speculative multi-token)
    block_tables: Optional[jax.Array] = None,  # (B, nb) i32: paged decode
    return_kv: bool = False,
    use_flash: bool = False,            # Pallas flash kernel (fwd-only paths)
) -> tuple[jax.Array, Optional[dict]]:
    """Returns (out, extra).

    Modes:
      cache=None                plain masked attention; extra = (k, v) if
                                ``return_kv`` (prefill builds caches from it).
      cache + cache_pos         update-then-attend (decode). Ring-buffer
                                layout when S_cache == window, else linear.
                                extra = new cache dict.
      cache + cache_pos +       block-paged decode: cache holds the SHARED
        block_tables            pools (num_blocks, block_size, Hkv, hd); the
                                new row is scattered into its pool block and
                                attention reads the pool through the table —
                                no dense per-step gather.  extra = new pools.
      cache, cache_pos=None     read-only cache (cross-attention); extra=None.

    ``cache_pos`` may be a per-row ``(B,)`` vector (ragged decode, T == 1):
    each row scatters its new K/V at its OWN position and masks its own
    history — the serving engine fuses slots at arbitrary positions into
    one step this way.  A scalar keeps the seed single-position semantics
    byte-for-byte (and supports T > 1 in the linear branch).

    ``cache_pos`` may also be a ``(B, T)`` matrix — MULTI-TOKEN ragged
    decode: row ``b``'s query ``t`` writes its K/V at ``cache_pos[b, t]``
    and masks ``kv_pos <= cache_pos[b, t]``.  Two callers ride this one
    branch: the speculative-decoding step (last committed token at
    t = 0, drafts after — one forward verifies a whole draft window per
    row) and the CHUNKED-PREFILL step (consecutive prompt positions —
    the ascending-position mask is exactly within-chunk causal attention
    plus full visibility of earlier chunks already in the cache).  Rows
    narrower than T repeat their last real (token, position) pair: the
    duplicate query recomputes the identical K/V row into the identical
    cache cell, so padding is a no-op and decode rows, draft windows and
    prefill chunks mix in one call.  Supported by the paged and linear
    branches (sliding-window ring buffers and recurrent state cannot
    rewind a rejected draft or grow chunk-by-chunk, so speculation and
    chunking never reach them).

    The same idempotent-rewrite property is what lets the DEVICE-
    RESIDENT decode loop (``api.serve_decode_multi``) carry this layer
    inside ``lax.while_loop``: rows that have halted (eos, emit cap)
    simply repeat their last (token, position) each remaining iteration
    — every branch here is pure traced jax (scatter + masked attention,
    no host callbacks), so the whole stack is closed under the loop and
    a halted row's re-scatter lands the identical value on the
    identical cell.  Ring buffers and recurrent state are excluded for
    the same reason as above: their cache update is not idempotent
    under a repeated (token, position).
    """
    dt = x.dtype
    B, T, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    read_only = cache is not None and cache_pos is None
    with jax.named_scope("qkv_proj"):
        q = _split_heads((x @ p["wq"].astype(dt)), hq, hd)
        if read_only:                                # read-only (cross-attn)
            k, v = cache["k"].astype(dt), cache["v"].astype(dt)
        else:
            src = x if kv_x is None else kv_x
            k = _split_heads((src @ p["wk"].astype(dt)), hkv, hd)
            v = _split_heads((src @ p["wv"].astype(dt)), hkv, hd)

        if "q_norm" in p:
            q = rms_norm(q, p["q_norm"], cfg.norm_eps)
            if not read_only:
                k = rms_norm(k, p["k_norm"], cfg.norm_eps)
        if kv_x is None and not read_only:
            # self-attention: rotate (cross-attn and read-only skip)
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    # cache entries hold post-norm, post-rope K (what decode appends)
    new_kv = None if read_only else (k, v)

    if (cache is not None and cache_pos is not None
            and block_tables is not None and window is None):
        # Block-paged decode (T == 1): scatter the new K/V row into its
        # pool block, then attend straight off the pool via the block
        # table (online softmax over valid blocks only).  Paging covers
        # the UNBOUNDED linear KV only — a windowed layer's ring buffer
        # (B, window, Hkv, hd) is shape-indistinguishable from a pool,
        # so the window guard here keeps a stray block_tables from
        # scattering into ring rows; windowed layers fall through to the
        # ring path below and ignore the table (matching PagedKVStore,
        # which never pages windowed configs).  The pool is shared
        # across slots and replicated across devices, so the
        # decode_shard_constraints pins for the per-slot dense cache do
        # not apply here.
        bs = cache["k"].shape[1]
        # per-row (and, multi-token, per-query) positions: scatter each
        # new K/V row at its own (block, offset) and attend over its own
        # history — one call serves a ragged batch and a draft window.
        # A scalar cache_pos broadcasts (uniform batch); (B,) bases a
        # consecutive window; (B, T) is explicit per-query.
        cp = jnp.asarray(cache_pos, jnp.int32)
        if cp.ndim == 2:
            cpm = cp                                            # (B, T)
        elif cp.ndim == 1:
            cpm = cp[:, None] + jnp.arange(T)
        else:
            cpm = (cp + jnp.arange(T))[None]
        cpm = jnp.broadcast_to(cpm, (B, T))
        with jax.named_scope("kv_write"):
            blk = jnp.take_along_axis(block_tables, cpm // bs,
                                      axis=1)                   # (B, T)
            off = cpm % bs
            ck = cache["k"].at[blk, off].set(k.astype(cache["k"].dtype))
            cv = cache["v"].at[blk, off].set(v.astype(cache["v"].dtype))
        from repro.kernels import ops as kernel_ops

        if _ATTN_TAP is not None:
            _ATTN_TAP.append((q, ck, cv, block_tables, cpm))
        with jax.named_scope("attention"):
            if T == 1:
                o = kernel_ops.paged_attention(
                    q[:, 0], ck, cv, block_tables, cpm[:, 0],
                    use_pallas=cfg.use_pallas,
                    attn_approx=cfg.attn_approx, window=cfg.attn_window)
            else:
                o = kernel_ops.paged_attention(
                    q, ck, cv, block_tables, cpm, use_pallas=cfg.use_pallas,
                    attn_approx=cfg.attn_approx, window=cfg.attn_window)
        with jax.named_scope("out_proj"):
            out = o.reshape(B, T, hq * hd).astype(dt) @ p["wo"].astype(dt)
        return out, {"k": ck, "v": cv}

    extra = None
    if cache is not None and cache_pos is not None:
        s_cache = cache["k"].shape[1]
        ragged = jnp.ndim(cache_pos) == 1       # per-row positions (T == 1)
        raggedT = jnp.ndim(cache_pos) == 2      # per-(row, query) positions
        bidx = jnp.arange(B)
        if window is not None and s_cache == window:
            # ring buffer: slot = pos % window (T must be 1)
            slot = cache_pos % window
            if ragged:
                ck = cache["k"].at[bidx, slot].set(
                    k[:, 0].astype(cache["k"].dtype))
                cv = cache["v"].at[bidx, slot].set(
                    v[:, 0].astype(cache["v"].dtype))
                s_idx = jnp.arange(s_cache)
                age = (cache_pos[:, None] - s_idx[None, :]) % window
                kv_pos = cache_pos[:, None] - age    # (B, S) absolute pos
                mask = (kv_pos >= 0)[:, None, None, :]
            else:
                ck = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
                cv = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
                s_idx = jnp.arange(s_cache)
                age = (cache_pos - s_idx) % window   # 0 for current slot
                kv_pos = cache_pos - age             # absolute pos per slot
                valid = kv_pos >= 0
                mask = valid[None, None, None, :]
        else:
            if raggedT:
                # multi-token ragged (speculative): each (row, query)
                # writes at its own position and masks its own history;
                # repeated (token, position) padding pairs rewrite the
                # same cell with the same value.
                ck = cache["k"].at[bidx[:, None], cache_pos].set(
                    k.astype(cache["k"].dtype))
                cv = cache["v"].at[bidx[:, None], cache_pos].set(
                    v.astype(cache["v"].dtype))
                kv_pos = jnp.arange(s_cache)
                m = kv_pos[None, None, :] <= cache_pos[:, :, None]  # (B,T,S)
                if window is not None:
                    m &= kv_pos[None, None, :] > (cache_pos[:, :, None]
                                                  - window)
                mask = m[:, None]                             # (B, 1, T, S)
            elif ragged:
                ck = cache["k"].at[bidx, cache_pos].set(
                    k[:, 0].astype(cache["k"].dtype))
                cv = cache["v"].at[bidx, cache_pos].set(
                    v[:, 0].astype(cache["v"].dtype))
                kv_pos = jnp.arange(s_cache)
                m = kv_pos[None, :] <= cache_pos[:, None]     # (B, S)
                if window is not None:
                    m &= kv_pos[None, :] > (cache_pos[:, None] - window)
                mask = m[:, None, None, :]
            else:
                ck = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype),
                    (0, cache_pos, 0, 0))
                cv = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype),
                    (0, cache_pos, 0, 0))
                kv_pos = jnp.arange(s_cache)
                q_abs = cache_pos + jnp.arange(T)
                m = kv_pos[None, :] <= q_abs[:, None]
                if window is not None:
                    m &= kv_pos[None, :] > (q_abs[:, None] - window)
                mask = m[None, None, :, :]
        extra = {"k": ck, "v": cv}
        k, v = ck.astype(dt), cv.astype(dt)
    elif cache is not None:                         # read-only: attend to all
        mask = None
    else:
        q_pos = positions if positions.ndim == 1 else positions[0]
        if causal and kv_x is None:
            kv_pos = q_pos
            m = kv_pos[None, :] <= q_pos[:, None]
            if window is not None:
                m &= kv_pos[None, :] > (q_pos[:, None] - window)
            mask = m[None, None, :, :]          # (1,1,1,T,S)
        else:
            mask = None
        if return_kv:
            extra = new_kv

    # GQA via explicit KV repeat: keeps the head axis cleanly TP-shardable
    # and lets a seq-sharded decode cache lower to partial-softmax + tiny
    # all-reduces under GSPMD (DESIGN.md §4).
    from repro.parallel.constraints import constrain

    decoding = cache is not None and cache_pos is not None
    if decoding and cfg.decode_shard_constraints:
        # Pin the partial-softmax pattern: cache stays SEQ-sharded; scores
        # are S-sharded; softmax stats + PV contraction become tiny
        # all-reduces. (Without this GSPMD all-gathers K AND V per layer —
        # measured 2.27 GB/dev/layer on qwen3-32b decode; §Perf iteration 1.)
        k = constrain(k, "batch", "model", None, None)
        v = constrain(v, "batch", "model", None, None)
        if extra is not None:
            extra = {"k": constrain(extra["k"], "batch", "model", None, None),
                     "v": constrain(extra["v"], "batch", "model", None,
                                    None)}
    if (use_flash and cache is None and kv_x is None
            and not cfg.seq_parallel_attn):
        # Pallas flash attention (prefill / fwd-only): scores never reach
        # HBM; GQA-native (no KV repeat); causal + sliding window.
        from repro.kernels import ops as kernel_ops
        from repro.kernels.flash_attention import flash_attention

        _, interp = kernel_ops.resolve_flags(True, None)
        o = flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal, window=window,
            interpret=interp)
        out = o.transpose(0, 2, 1, 3).reshape(B, T, hq * hd)
        extra = new_kv if return_kv else None
        return out @ p["wo"].astype(dt), extra

    seq_par = cfg.seq_parallel_attn and not decoding and cache is None
    if seq_par:
        # Context parallelism: shard the QUERY sequence over 'model'
        # (weights are replicated over 'model' by the matching param rule).
        # The fix for head counts that do not divide TP (§Perf iteration 2).
        q = constrain(q, "batch", "model", None, None)
        k = constrain(k, "batch", None, None, None)
        v = constrain(v, "batch", None, None, None)
    g = hq // hkv
    if decoding and g > 1:
        # Decode: grouped-query einsum — repeating K/V to hq heads would
        # materialize g x the cache per step (measured +68 GB/dev reads on
        # qwen3-32b; §Perf iter 2). Head sharding is irrelevant here (the
        # cache is SEQ-sharded), so the grouped form costs nothing.
        qg = q.reshape(B, T, hkv, g, hd)
        scores = jnp.einsum("btkgh,bskh->bkgts", qg, k) / math.sqrt(hd)
        scores = scores.astype(jnp.float32)
        if cfg.decode_shard_constraints:
            scores = constrain(scores, "batch", None, None, None, "model")
        if mask is not None:
            scores = jnp.where(mask[:, :, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(dt)
        out = jnp.einsum("bkgts,bskh->btkgh", probs, v).reshape(
            B, T, hq * hd)
        return out @ p["wo"].astype(dt), extra
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    scores = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(hd)
    scores = scores.astype(jnp.float32)
    if decoding and cfg.decode_shard_constraints:
        scores = constrain(scores, "batch", None, None, "model")
    if seq_par:
        scores = constrain(scores, "batch", None, "model", None)
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(dt)
    out = jnp.einsum("bhts,bshd->bthd", probs, v).reshape(B, T, hq * hd)
    if seq_par:
        out = constrain(out, "batch", "model", None)
    return out @ p["wo"].astype(dt), extra


# ---------------------------------------------------------------------------
# MoE (top-k, capacity factor). Two executors + single-device oracle.
# ---------------------------------------------------------------------------
def init_moe(key, cfg: ModelConfig):
    m = cfg.moe
    d, fe, e = cfg.d_model, m.d_ff_expert, m.num_experts
    ks = jax.random.split(key, 5)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(fe)
    p = {
        "router": jax.random.normal(ks[0], (d, e)) * s_in,
        "w_gate": jax.random.normal(ks[1], (e, d, fe)) * s_in,
        "w_up": jax.random.normal(ks[2], (e, d, fe)) * s_in,
        "w_out": jax.random.normal(ks[3], (e, fe, d)) * s_out,
    }
    if m.shared_expert:
        p["shared"] = init_mlp(ks[4], d, fe, cfg.activation)
    return p


def _route(xf: jax.Array, router: jax.Array, spec: MoESpec):
    """Per-token routing: probs (N,E) f32, top-k (vals, idx)."""
    logits = (xf.astype(jnp.float32) @ router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, spec.top_k)
    if spec.top_k > 1:
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    return probs, vals, idx


def _aux_loss(probs: jax.Array, idx: jax.Array, n_experts: int) -> jax.Array:
    """Switch-style load-balancing loss: E * sum_e f_e * P_e."""
    onehot = jax.nn.one_hot(idx, n_experts, dtype=jnp.float32)  # (N,k,E)
    f = jnp.mean(jnp.sum(onehot, axis=1), axis=0)  # fraction routed
    p = jnp.mean(probs, axis=0)
    return n_experts * jnp.sum(f * p)


def moe_dense_oracle(p, x: jax.Array, cfg: ModelConfig):
    """Single-device reference: every expert over every token, masked.
    No capacity drops — exact; used by smoke tests / kernels oracles."""
    spec = cfg.moe
    B, T, d = x.shape
    xf = x.reshape(-1, d)
    probs, vals, idx = _route(xf, p["router"], spec)
    out = jnp.zeros_like(xf, dtype=jnp.float32)
    for e in range(spec.num_experts):
        pe = {k: p[k][e] for k in ("w_gate", "w_up", "w_out")}
        ye = mlp({"w_gate": pe["w_gate"], "w_up": pe["w_up"],
                  "w_out": pe["w_out"]}, xf, cfg.activation)
        w_e = jnp.sum(jnp.where(idx == e, vals, 0.0), axis=-1)  # (N,)
        out += w_e[:, None] * ye.astype(jnp.float32)
    if spec.shared_expert:
        out += mlp(p["shared"], xf, cfg.activation).astype(jnp.float32)
    aux = _aux_loss(probs, idx, spec.num_experts)
    return out.astype(x.dtype).reshape(B, T, d), aux


def moe_gshard(p, x: jax.Array, cfg: ModelConfig, group_size: int = 4096):
    """GShard-style grouped one-hot dispatch einsums (pjit-friendly).

    Groups along the token axis keep the dispatch tensors bounded:
    dispatch is (G, n, E, C) with C = ceil(cf * n * k / E). This is the
    paper-era EP baseline; the §Perf hillclimb replaces it with the
    shard_map EP executor (moe_ep) for collective-bound shapes.
    """
    spec = cfg.moe
    B, T, d = x.shape
    n = min(group_size, T)
    gpb = T // n                      # groups per batch row
    xg = x.reshape(B * gpb, n, d)
    G = B * gpb
    e_num = spec.num_experts
    cap = max(1, int(math.ceil(spec.capacity_factor * n * spec.top_k / e_num)))

    probs, vals, idx = _route(xg.reshape(-1, d), p["router"], spec)
    aux = _aux_loss(probs, idx, e_num)
    vals = vals.reshape(G, n, spec.top_k)
    idx = idx.reshape(G, n, spec.top_k)

    onehot = jax.nn.one_hot(idx, e_num, dtype=jnp.float32)       # (G,n,k,E)
    # rank of each (token, choice) within its expert, k-major order
    flat = onehot.reshape(G, n * spec.top_k, e_num)
    ranks = jnp.cumsum(flat, axis=1) - flat                       # 0-based
    ranks = jnp.sum(ranks * flat, axis=-1).reshape(
        G, n, spec.top_k).astype(jnp.int32)
    keep = ranks < cap
    capslot = jax.nn.one_hot(jnp.where(keep, ranks, cap), cap,
                             dtype=jnp.float32)                   # (G,n,k,C)
    # (G, n, E, C) combine/dispatch tensors
    combine = jnp.einsum("gnk,gnke,gnkc->gnec",
                         vals * keep, onehot, capslot)
    dispatch = (combine > 0).astype(x.dtype)

    expert_in = jnp.einsum("gnec,gnd->egcd", dispatch, xg)
    if is_glu(cfg.activation):
        h = activate(jnp.einsum("egcd,edf->egcf", expert_in, p["w_gate"].astype(x.dtype)),
                     jnp.einsum("egcd,edf->egcf", expert_in, p["w_up"].astype(x.dtype)),
                     cfg.activation)
    else:
        h = activate(jnp.einsum("egcd,edf->egcf", expert_in, p["w_gate"].astype(x.dtype)),
                     None, cfg.activation)
    expert_out = jnp.einsum("egcf,efd->egcd", h, p["w_out"].astype(x.dtype))
    y = jnp.einsum("gnec,egcd->gnd", combine.astype(x.dtype), expert_out)
    y = y.reshape(B, T, d)
    if spec.shared_expert:
        y = y + mlp({k: v.astype(x.dtype) for k, v in p["shared"].items()},
                    x, cfg.activation)
    return y, aux


def moe_scatter(p, x: jax.Array, cfg: ModelConfig):
    """Scatter/gather dispatch into a global (E*C, D) buffer.

    For small token counts (decode): buffer is tiny, FLOPs ~= cf * active.
    """
    spec = cfg.moe
    B, T, d = x.shape
    xf = x.reshape(-1, d)
    n_tok = xf.shape[0]
    e_num = spec.num_experts
    cap = max(1, int(math.ceil(
        spec.capacity_factor * n_tok * spec.top_k / e_num)))

    probs, vals, idx = _route(xf, p["router"], spec)
    aux = _aux_loss(probs, idx, e_num)
    onehot = jax.nn.one_hot(idx, e_num, dtype=jnp.float32)  # (N,k,E)
    flat = onehot.reshape(n_tok * spec.top_k, e_num)
    ranks = (jnp.cumsum(flat, axis=0) - flat)
    ranks = jnp.sum(ranks * flat, axis=-1).astype(jnp.int32)  # (N*k,)
    fidx = idx.reshape(-1)
    keep = ranks < cap
    dest = jnp.where(keep, fidx * cap + ranks, e_num * cap)  # drop -> OOB

    xrep = jnp.repeat(xf, spec.top_k, axis=0)                # (N*k, d)
    buf = jnp.zeros((e_num * cap + 1, d), x.dtype).at[dest].add(xrep)
    ein = buf[:-1].reshape(e_num, cap, d)
    if is_glu(cfg.activation):
        h = activate(jnp.einsum("ecd,edf->ecf", ein, p["w_gate"].astype(x.dtype)),
                     jnp.einsum("ecd,edf->ecf", ein, p["w_up"].astype(x.dtype)),
                     cfg.activation)
    else:
        h = activate(jnp.einsum("ecd,edf->ecf", ein, p["w_gate"].astype(x.dtype)),
                     None, cfg.activation)
    eout = jnp.einsum("ecf,efd->ecd", h, p["w_out"].astype(x.dtype))
    flatout = jnp.concatenate(
        [eout.reshape(e_num * cap, d), jnp.zeros((1, d), x.dtype)], axis=0)
    per_choice = flatout[dest] * (vals.reshape(-1, 1) * keep[:, None]).astype(x.dtype)
    y = per_choice.reshape(n_tok, spec.top_k, d).sum(axis=1)
    y = y.reshape(B, T, d)
    if spec.shared_expert:
        y = y + mlp({k: v.astype(x.dtype) for k, v in p["shared"].items()},
                    x, cfg.activation)
    return y, aux


def moe_layer(p, x, cfg: ModelConfig, *, impl: str = "gshard",
              group_size: int = 4096):
    if impl == "oracle":
        return moe_dense_oracle(p, x, cfg)
    if impl == "gshard":
        return moe_gshard(p, x, cfg, group_size=group_size)
    if impl == "scatter":
        return moe_scatter(p, x, cfg)
    if impl == "ep":
        from repro.parallel.moe_ep import moe_ep  # local: avoid cycle
        return moe_ep(p, x, cfg)
    raise ValueError(impl)
