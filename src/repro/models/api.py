"""Public model API: loss, serve steps, input specs for every (arch, shape).

Serve heads are ``Sampler`` objects (``repro.serve.sampler``): one
protocol — device-side ``head()``, host-side ``pick()`` — behind which
every variant lives:

  SoftmaxBaseline   BASELINE: materialize softmax probabilities over the
                    vocab, then take the max — what a
                    probability-reporting accelerator must do.
  Greedy('reduced') THE PAPER: greedy class = argmax of raw logits; no
                    exp, no normalizing sum, no divide. Bit-identical
                    predictions (Theorem 1), strictly less work.
  Greedy('fused')   BEYOND-PAPER: reduced head via the Pallas kernel —
                    logits are never materialized in HBM.
  Greedy('sharded') multi-chip: per-vocab-shard comparator + tiny combine.
  TopK / Temperature  sampling via the k-winner bus / Gumbel-max.

``serve_*`` accept a Sampler or a legacy ``head_mode`` string
(resolved by ``sampler.resolve`` — the single string switch).

``serve_decode(..., block_tables=...)`` runs decode attention straight
off the block-paged KV pool (no dense gather): the cache tree's linear
K/V leaves are the shared ``(layers, num_blocks, block_size, Hkv, hd)``
pools and the block table maps each batch row's positions onto them.
Decode is RAGGED — ``pos`` may be a per-row ``(B,)`` vector, so one call
serves rows at arbitrary sequence lengths.  ``serve_prefill_paged``
makes admission paged-native: the prompt's K/V is scattered into pool
blocks inside the jitted prefill, no host round-trip of a dense cache.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeSpec
from repro.models import lm
from repro.models.layers import cdtype


def _as_sampler(head_mode, cfg: ModelConfig):
    """Resolve + validate: invalid head/config combinations (e.g. a top-k
    bus on the softmax baseline) raise here instead of silently serving
    the reduced path — a faked baseline would poison every A/B claim."""
    from repro.serve.sampler import resolve

    return resolve(head_mode, cfg=cfg)


# ---------------------------------------------------------------------------
# Loss (SPMD-friendly: no gather over the sharded vocab axis)
# ---------------------------------------------------------------------------
def xent_loss(logits: jax.Array, labels: jax.Array,
              mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean softmax-CE. logits (..., V) f32; labels (...) int32."""
    logits = logits.astype(jnp.float32)
    m = jnp.max(logits, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1))
    # one-hot-free label pick: SPMD-partitions cleanly over a sharded vocab
    viota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                     logits.ndim - 1)
    hit = viota == labels[..., None]
    lab_logit = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
    per_tok = lse - lab_logit
    if mask is None:
        return jnp.mean(per_tok)
    mask = mask.astype(jnp.float32)
    return jnp.sum(per_tok * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def train_loss(params, cfg: ModelConfig, batch: dict) -> jax.Array:
    logits, aux = lm.forward(params, cfg, batch)
    loss = xent_loss(logits, batch["labels"], batch.get("loss_mask"))
    if cfg.moe is not None:
        loss = loss + cfg.moe.router_aux_weight * aux
    return loss


# ---------------------------------------------------------------------------
# Serving steps
# ---------------------------------------------------------------------------
def serve_prefill(params, cfg: ModelConfig, batch: dict, max_len: int,
                  head_mode="reduced"):
    """Prompt pass: returns (head output (B, ...), cache).

    ``head_mode``: a Sampler or a legacy string ('reduced' | 'fused' |
    'sharded' | 'softmax' | 'temperature').
    """
    s = _as_sampler(head_mode, cfg)
    h, cache = lm.prefill(params, cfg, batch, max_len)
    return s.head(params, cfg, h), cache


def serve_decode(params, cfg: ModelConfig, token: jax.Array, cache,
                 pos: jax.Array, head_mode="reduced", *,
                 block_tables: Optional[jax.Array] = None):
    """One token step: returns (head output (B, ...), new_cache).

    ``pos`` is a scalar, a per-row ``(B,)`` vector — ragged decode:
    every batch row at its own position in one call — or a per-(row,
    query) ``(B, T)`` matrix when ``token`` is a (B, T) window: a
    speculative draft window (the head then applies to the NEXT-token
    hidden state, position 0; use ``kernels.ops.verify_draft`` on
    ``lm.decode_step``'s full (B, T, D) output to verify drafts) or a
    prefill CHUNK of consecutive prompt positions (the serving engine's
    chunked admission — it gathers the LAST hidden column itself and
    discards mid-prompt logits).  With ``block_tables`` the cache's
    linear K/V leaves are block-paged pools: the step scatters the new
    row(s) into their pool blocks and attention reads the pool through
    the table — no dense gather.
    """
    s = _as_sampler(head_mode, cfg)
    h, new_cache = lm.decode_step(params, cfg, token, cache, pos,
                                  block_tables=block_tables)
    if h.ndim == 3:                  # multi-token window: next-token head
        h = h[:, 0]
    return s.head(params, cfg, h), new_cache


def serve_decode_multi(params, cfg: ModelConfig, token: jax.Array, cache,
                       pos: jax.Array, keys: jax.Array,
                       emit_caps: jax.Array, row_sets, *, steps: int,
                       eos_id: int, samplers,
                       block_tables: Optional[jax.Array] = None):
    """Device-resident multi-step decode: up to ``steps`` fused
    iterations inside ONE ``lax.while_loop`` — trunk forward, K/V
    scatter, comparator/sampler head and the feed-back of the sampled
    token all stay on device; the host sees nothing until the loop
    exits.  This is the ``host_stride`` engine's dispatch unit: one
    host round-trip amortized over up to ``steps`` tokens.

    The loop carry is ``(step, tokens (B,), positions (B,), cache,
    keys (B, 2), emitted (B,), halted (B,), out (B, steps))``.  Each
    iteration runs ``lm.decode_step`` at T=1 over ALL rows (inactive
    rows repeat their last (token, position) — the repeat-last padding
    convention makes the K/V rewrite idempotent, which is why this
    path requires pure-attention stacks), samples the next token per
    sampler group via ``Sampler.sample_device`` with per-row PRNG keys
    split once per EMITTED token, and early-exits when every row is
    done.  Device-side stop conditions per row:

      * ``emit_caps[b]`` tokens emitted — the engine folds the per-row
        ``max_new_tokens`` remainder, the ``max_len`` ceiling and the
        slot's block-table capacity into this one cap;
      * the sampled token equals ``eos_id`` (the eos token itself IS
        emitted, then the row halts; pass ``eos_id=-1`` to disable);

    Stop SEQUENCES are not matched here — the engine drains ``out``
    through its per-token emission path and trims at the match (a
    bounded lag of at most ``steps - 1`` extra tokens, KV rewound via
    ``PagedKVStore.rewind``).

    ``samplers`` / ``row_sets``: per-group full samplers (static; the
    jit key — temperature lives ON DEVICE here, unlike the legacy
    step's ``device_form()`` grouping) and their pow2-padded traced
    row-index sets.  Returns ``(out (B, steps) int32 with -1 padding,
    emitted (B,) int32, new_keys (B, 2), new_cache)``.
    """
    i32 = jnp.int32
    B = token.shape[0]

    def cond(c):
        step, _, _, _, _, emitted, halted, _ = c
        return (step < steps) & jnp.any(~halted & (emitted < emit_caps))

    def body(c):
        step, tok, p, cch, ks, emitted, halted, out = c
        active = ~halted & (emitted < emit_caps)
        h, new_cch = lm.decode_step(params, cfg, tok[:, None], cch, p,
                                    block_tables=block_tables)
        split = jax.vmap(jax.random.split)(ks)
        next_keys, use_keys = split[:, 0], split[:, 1]
        sampled = tok
        with jax.named_scope("head"):
            for s, rows in zip(samplers, row_sets):
                ids = s.sample_device(params, cfg, h[rows], use_keys[rows])
                sampled = sampled.at[rows].set(ids.astype(i32))
        new_tok = jnp.where(active, sampled, tok)
        new_p = jnp.where(active, p + 1, p)
        out = out.at[:, step].set(jnp.where(active, sampled, out[:, step]))
        new_halted = halted | (active & (sampled == eos_id))
        new_emitted = emitted + active.astype(i32)
        new_ks = jnp.where(active[:, None], next_keys, ks)
        return (step + 1, new_tok, new_p, new_cch, new_ks,
                new_emitted, new_halted, out)

    init = (jnp.asarray(0, i32), token.astype(i32), pos.astype(i32),
            cache, keys, jnp.zeros((B,), i32),
            jnp.zeros((B,), jnp.bool_), jnp.full((B, steps), -1, i32))
    (_, _, _, new_cache, new_keys, emitted, _, out) = jax.lax.while_loop(
        cond, body, init)
    return out, emitted, new_keys, new_cache


def serve_prefill_paged(params, cfg: ModelConfig, batch: dict,
                        cache_len: int, head_mode="reduced", *,
                        pools, blocks: jax.Array, paged_mask):
    """One-shot paged-native prompt pass (B = 1): prefill at the
    block-aligned ``cache_len`` and scatter the paged K/V leaves
    straight into the SHARED pool blocks, all inside one jitted call —
    the dense prefill cache never round-trips through the host (the old
    path returned the full cache, which the store then re-read,
    re-blocked and scattered a second time).

    This is the LEGACY admission path: the fused scheduler with
    ``chunk_size`` set serves prompts through ``lm.decode_step``'s
    (B, T) paged branch instead — ``chunk_size`` tokens per engine
    iteration beside the decode rows, no separate prefill call.
    One-shot remains the path for the cohort scheduler, dense layouts,
    and configs with non-paged cache leaves (ring buffers, recurrent
    state), and the byte-identity oracle chunked output is tested
    against.

    ``pools``: the store's pool list (None where a leaf is dense);
    ``blocks``: (nb,) int32 pool blocks freshly allocated for this slot;
    ``paged_mask``: which cache leaves (in ``jax.tree.flatten`` order)
    are paged.  Returns (head output, new_pools, dense_leaves) where
    ``dense_leaves`` holds the non-paged cache leaves (ring buffers,
    recurrent state, cross-attention K/V) for the store to copy into the
    slot's dense row.
    """
    s = _as_sampler(head_mode, cfg)
    h, cache = lm.prefill(params, cfg, batch, cache_len)
    leaves = jax.tree.flatten(cache)[0]
    nb = blocks.shape[0]
    new_pools, dense_leaves = [], []
    for m, pool, leaf in zip(paged_mask, pools, leaves):
        if m:
            bs = pool.shape[2]
            view = leaf[:, 0, :nb * bs]               # (L, nb*bs, Hkv, hd)
            blk = view.reshape(view.shape[0], nb, bs, *view.shape[2:])
            new_pools.append(pool.at[:, blocks].set(blk.astype(pool.dtype)))
            dense_leaves.append(None)
        else:
            new_pools.append(None)
            dense_leaves.append(leaf)
    return s.head(params, cfg, h), new_pools, dense_leaves


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins; no allocation) per (arch, shape)
# ---------------------------------------------------------------------------
def batch_struct(cfg: ModelConfig, shape: ShapeSpec) -> dict:
    """Host-side batch spec for the given input shape (train/prefill)."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    dt = cdtype(cfg)
    if cfg.n_encoder_layers:
        # enc-dec: frontend STUB supplies precomputed frame embeddings.
        b = {
            "src_embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model), dt),
            "tokens": jax.ShapeDtypeStruct((B, S), i32),
        }
    elif cfg.num_image_tokens:
        b = {
            "image_embeds": jax.ShapeDtypeStruct(
                (B, cfg.num_image_tokens, cfg.d_model), dt),
            "tokens": jax.ShapeDtypeStruct((B, S), i32),
        }
    else:
        b = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
    if shape.kind == "train":
        b["labels"] = jax.ShapeDtypeStruct((B, S), i32)
    return b


def cache_struct(params_struct, cfg: ModelConfig, batch_size: int,
                 max_len: int):
    """Decode-cache spec via eval_shape (no allocation)."""
    enc_struct = None
    if cfg.n_encoder_layers:
        enc_struct = jax.ShapeDtypeStruct(
            (batch_size, max_len, cfg.d_model), cdtype(cfg))

    def mk(params, enc):
        return lm.init_cache(params, cfg, batch_size, max_len, enc)

    if enc_struct is None:
        return jax.eval_shape(lambda p: lm.init_cache(
            p, cfg, batch_size, max_len), params_struct)
    return jax.eval_shape(mk, params_struct, enc_struct)


def params_struct(cfg: ModelConfig):
    return jax.eval_shape(
        lambda k: lm.init_params(cfg, k), jax.random.PRNGKey(0))
