"""Paged (block) KV cache for the serving engine.

The dense engine reserved ``n_slots x max_len`` KV rows up front — a
sequence at position 30 pinned 256 rows.  The paged store instead splits
the linear (full-attention) K/V leaves of the model cache into fixed-size
blocks drawn from one shared pool:

  - per slot, a BLOCK TABLE maps view positions ``[b * block_size, ...)``
    to pool blocks; blocks are allocated on demand as the sequence grows
    and returned to the FREE LIST the moment the request completes;
  - decode attention reads the pool IN PLACE through the block table
    (``kernels/paged_attention.py``) over exactly
    ``ceil((pos+1)/block_size)`` blocks per slot, so reads scale with
    the sequence's real length, not ``max_len`` — and nothing ever
    copies the pool into a dense per-step view;
  - speculative draft windows grow a slot by several positions at once
    (``ensure_capacity`` to the window's last write) and REWIND in O(1)
    when drafts are rejected (``rewind``: surplus whole blocks straight
    back to the free list; the stale rows behind the position masks are
    simply overwritten later);
  - CHUNKED prefill allocates incrementally: admission reserves only the
    first chunk's cover (``can_admit(S, chunk_size)``) and each later
    chunk extends the slot's table by its own cover
    (``ensure_capacity`` again), so the door check
    (``can_ever_admit(S, chunk_size)``) needs only the final residency
    ``blocks_for(S + 1)`` — not the one-shot cover-plus-decode-block;
  - PREFIX SHARING: completed / preempted / cancelled requests publish
    their full-block token runs into a ``PrefixTrie``
    (``release(slot, publish_tokens=...)``) instead of freeing them;
    admission maps the longest cached run into a new slot's table
    (``adopt_prefix``) so concurrent requests with a common prefix
    attend through the SAME pool blocks and prefill only their suffix.
    Blocks are REFCOUNTED and every write path is copy-on-write
    (``cow_for_write``/``ensure_capacity``/``rewind``); cached runs are
    LRU-evicted under pool pressure, and ``can_admit`` counts them as
    free, so caching never shrinks the schedulable pool;
  - non-linear cache state is NOT paged: sliding-window ring buffers are
    already O(window), recurrent (RG-LRU / RWKV) state is O(1), and
    cross-attention K/V is read-only — those stay dense per-slot.

The split is decided per cache LEAF from its shape (the linear attention
layout is ``(layers, B, max_len, n_kv_heads, head_dim)``), so every
architecture family in the zoo works: pure-attention models page all
their KV, hybrid/ssm models page nothing and degrade gracefully to the
dense layout for their O(1)/O(window) state.

Numerics: a gathered ``nb * block_size`` view is masked exactly like the
dense ``max_len`` view (``kv_pos <= pos``; masked scores are -1e30, whose
exp underflows to exactly 0.0 in f32), so paged and dense decode agree on
greedy outputs — asserted token-exactly by tests/test_serve_paged.py.
"""
from __future__ import annotations

import collections
import math
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import lm


def pow2(n: int) -> int:
    """Next power of two >= n — the ONE shape-bucketing rule shared by
    the engine's batch/row-set padding and the block-table column
    padding (they must agree, or compiled shapes diverge)."""
    return 1 << (n - 1).bit_length()


class BlockAllocator:
    """REFCOUNTED free-list allocator over ``num_blocks`` pool blocks.

    A block may be referenced by several owners at once — the block
    tables of sibling slots sharing a prefix, plus the prefix trie —
    so ``free`` decrements and a block returns to the free list only
    when its last reference drops.  ``incref`` adds a reference to an
    already-live block (a prefix-cache hit mapping it into another
    slot's table).

    LIFO reuse (a stack) so recently-freed blocks — still warm in cache —
    are handed out first.  Double-free (freeing a block whose refcount
    already reached zero) and foreign-block frees raise.
    """

    def __init__(self, num_blocks: int):
        self.num_blocks = num_blocks
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._ref: dict = {}            # block id -> live reference count
        self.peak_in_use = 0            # pool high-watermark (capacity obs)

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_shared(self) -> int:
        """Blocks currently referenced more than once (prefix sharing)."""
        return sum(1 for c in self._ref.values() if c > 1)

    def refcount(self, block: int) -> int:
        return self._ref.get(block, 0)

    def alloc(self, n: int = 1) -> List[int]:
        if n > len(self._free):
            raise MemoryError(
                f"paged KV pool exhausted: need {n}, free {len(self._free)}")
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._ref[b] = 1
        in_use = self.num_blocks - len(self._free)
        if in_use > self.peak_in_use:
            self.peak_in_use = in_use
        return out

    def incref(self, blocks) -> None:
        for b in blocks:
            if b not in self._ref:
                raise ValueError(f"incref of unallocated block {b}")
            self._ref[b] += 1

    def free(self, blocks) -> None:
        for b in blocks:
            if b not in self._ref:
                raise ValueError(f"free of unallocated block {b}")
            self._ref[b] -= 1
            if self._ref[b] == 0:
                del self._ref[b]
                self._free.append(b)


class _TrieNode:
    __slots__ = ("key", "block", "parent", "children", "stamp")

    def __init__(self, key, block, parent):
        self.key = key              # the block's token run (len block_size)
        self.block = block
        self.parent = parent
        self.children: dict = {}
        self.stamp = 0


class PrefixTrie:
    """Full-block token-id runs -> cached pool block ids.

    Each node below the root holds ONE block keyed by its
    ``block_size``-token run; the path from the root spells the whole
    prefix, so a node's block caches the K/V of positions
    ``[depth*bs, (depth+1)*bs)`` for exactly that token prefix.  Causal
    attention makes this sound: K/V at position p is a function of
    ``tokens[:p+1]`` alone, so equal token prefixes mean equal blocks
    whichever request computed them.

    The trie holds one allocator reference per node.  Eviction is LRU
    over nodes whose block the trie alone references (refcount 1) —
    because a slot that matched a child necessarily matched (and still
    references) every ancestor, refcount-1 nodes always form whole
    subtrees and leaf-first eviction never strands a referenced child.
    """

    def __init__(self, block_size: int):
        self.block_size = block_size
        self.root = _TrieNode(None, None, None)
        self.nodes = 0
        self._clock = 0                 # monotonic LRU stamp source

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def match_prefix(self, tokens) -> tuple:
        """Longest cached whole-block run: ``([block ids], matched_len)``.

        Capped at ``(len(tokens) - 1) // block_size`` blocks so an
        admitted request always keeps >= 1 suffix token to prefill — the
        final chunk's head output is what emits its first token."""
        bs = self.block_size
        node, blocks = self.root, []
        stamp = self._tick()
        for d in range(max(0, (len(tokens) - 1) // bs)):
            child = node.children.get(
                tuple(int(t) for t in tokens[d * bs:(d + 1) * bs]))
            if child is None:
                break
            child.stamp = stamp
            blocks.append(child.block)
            node = child
        return blocks, len(blocks) * bs

    def publish(self, tokens, blocks) -> tuple:
        """Install a completed request's full-block run (``blocks[d]``
        covers ``tokens[d*bs:(d+1)*bs]``).  Returns ``(adopted, dupes)``:
        adopted blocks now live in new trie nodes (the caller's
        reference TRANSFERS to the trie); dupes were already cached
        under an existing node, so the caller should drop its reference
        — a dupe may be that node's own block when the publisher got it
        from a match in the first place."""
        bs = self.block_size
        stamp = self._tick()
        node, adopted, dupes = self.root, [], []
        for d, b in enumerate(blocks):
            key = tuple(int(t) for t in tokens[d * bs:(d + 1) * bs])
            child = node.children.get(key)
            if child is None:
                child = _TrieNode(key, b, node)
                node.children[key] = child
                self.nodes += 1
                adopted.append(b)
            else:
                dupes.append(b)
            child.stamp = stamp
            node = child
        return adopted, dupes

    def n_evictable(self, refcount) -> int:
        """Nodes whose block only the trie references.  These always
        form whole subtrees (see class docstring), so every one of them
        is reachable by repeated leaf-first eviction — the count is an
        exact reclaimable-block figure, not an optimistic bound."""
        n, stack = 0, [self.root]
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            if node is not self.root and refcount(node.block) == 1:
                n += 1
        return n

    def evict(self, n: int, refcount) -> List[int]:
        """Remove up to ``n`` least-recently-used refcount-1 LEAVES
        (re-scanning as parents become leaves) and return their blocks —
        never a block a slot still maps."""
        out: List[int] = []
        while len(out) < n:
            victim = None
            for node in self._leaves():
                if refcount(node.block) != 1:
                    continue
                if victim is None or node.stamp < victim.stamp:
                    victim = node
            if victim is None:
                break
            del victim.parent.children[victim.key]
            self.nodes -= 1
            out.append(victim.block)
        return out

    def _leaves(self):
        stack = [self.root]
        while stack:
            node = stack.pop()
            for c in node.children.values():
                if c.children:
                    stack.append(c)
                else:
                    yield c


class PagedKVStore:
    """Owns the pool + dense leaves of the engine cache and the per-slot
    block tables.  ``kv_layout='dense'`` is the degenerate store where no
    leaf is paged (exactly the seed engine's cache), used as the oracle.
    """

    def __init__(self, params, cfg: ModelConfig, *, n_slots: int,
                 max_len: int, block_size: int = 16,
                 num_blocks: Optional[int] = None, layout: str = "paged"):
        assert layout in ("paged", "dense"), layout
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.block_size = block_size
        self.max_blocks_per_slot = -(-max_len // block_size)
        if num_blocks is None:
            # default: same worst-case residency as the dense layout; pass
            # fewer to overcommit (the scheduler defers/preempts on empty).
            num_blocks = n_slots * self.max_blocks_per_slot

        struct = jax.eval_shape(
            lambda p: lm.init_cache(p, cfg, 1, max_len), params)
        leaves, self.treedef = jax.tree.flatten(struct)
        # Sliding-window models keep the dense layout outright: their ring
        # caches are already O(window), and a gathered view whose length
        # happened to equal the window would flip attention into ring
        # addressing.  Paging is for the UNBOUNDED linear KV only.
        windowed = cfg.attention_window is not None

        def _pageable(leaf) -> bool:
            return (layout == "paged"
                    and not windowed
                    and leaf.ndim == 5
                    and leaf.shape[1] == 1
                    and leaf.shape[2] == max_len
                    and leaf.shape[3] == cfg.n_kv_heads
                    and leaf.shape[4] == cfg.head_dim)

        self.paged_mask = [_pageable(l) for l in leaves]
        self.pools = [
            jnp.zeros((l.shape[0], num_blocks, block_size) + l.shape[3:],
                      l.dtype) if m else None
            for l, m in zip(leaves, self.paged_mask)
        ]
        self.denses = [
            None if m else jnp.zeros((l.shape[0], n_slots) + l.shape[2:],
                                     l.dtype)
            for l, m in zip(leaves, self.paged_mask)
        ]
        self.allocator = BlockAllocator(num_blocks)
        self.slot_blocks: List[List[int]] = [[] for _ in range(n_slots)]
        self.prefix_trie = PrefixTrie(block_size)
        self.cow_copies = 0
        self.prefix_evictions = 0
        # block -> number of slot tables mapping it, kept where tables
        # change (never by walking the trie): its size is ``live_blocks``
        self._live = collections.Counter()
        self.live_peak = 0

    @property
    def any_paged(self) -> bool:
        return any(self.paged_mask)

    def shard_pools(self, mesh) -> None:
        """Commit the paged pools to head-wise sharding over 'model':
        each device holds (and scatters into) only its kv-head slice of
        every pool.  The jitted steps take the pools as donated
        operands, so the committed layout propagates through GSPMD and
        ``write_back`` adopts equally-sharded outputs — no per-step
        resharding.  Leaves whose head count does not divide TP stay
        replicated (``paged_pool_specs``' drop rule)."""
        from jax.sharding import NamedSharding

        from repro.parallel import sharding as shard_rules

        specs = shard_rules.paged_pool_specs(self.pools, mesh)
        self.pools = [
            pool if spec is None
            else jax.device_put(pool, NamedSharding(mesh, spec))
            for pool, spec in zip(self.pools, specs)
        ]

    def usage(self) -> dict:
        """Pool occupancy snapshot (JSON-ready) — surfaced by the HTTP
        server's /v1/stats next to the engine counters."""
        a = self.allocator
        return {
            "layout": "paged" if self.any_paged else "dense",
            "block_size": self.block_size,
            "num_blocks": a.num_blocks,
            "blocks_free": a.n_free,
            "blocks_in_use": a.num_blocks - a.n_free,
            "paged_leaves": sum(self.paged_mask),
            "dense_leaves": len(self.paged_mask) - sum(self.paged_mask),
            "peak_in_use": a.peak_in_use,
            "shared_blocks": a.n_shared,
            "prefix_blocks": self.prefix_trie.nodes,
            "blocks_reclaimable": self.reclaimable_blocks,
            "cow_copies": self.cow_copies,
            "prefix_evictions": self.prefix_evictions,
            "live_blocks": self.live_blocks,
            "live_peak": self.live_peak,
        }

    # -- live blocks ---------------------------------------------------------
    @property
    def live_blocks(self) -> int:
        """Distinct blocks that live slots' tables map: what requests in
        flight hold, without the cached blocks only the trie keeps."""
        return len(self._live)

    def reset_live_peak(self) -> None:
        """Start ``live_peak`` over from the blocks live now (a
        measurement window's opening)."""
        self.live_peak = self.live_blocks

    def _map(self, blocks) -> None:
        for b in blocks:
            self._live[b] += 1
        self.live_peak = max(self.live_peak, len(self._live))

    def _unmap(self, blocks) -> None:
        for b in blocks:
            n = self._live[b] - 1
            if n > 0:
                self._live[b] = n
            else:
                self._live.pop(b, None)

    # -- block accounting ----------------------------------------------------
    def blocks_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.block_size) if self.any_paged else 0

    @property
    def reclaimable_blocks(self) -> int:
        """Trie-held blocks no slot maps — evictable on demand."""
        return self.prefix_trie.n_evictable(self.allocator.refcount)

    def _effective_free(self) -> int:
        """Blocks available to a new allocation: the free list plus
        what trie eviction can reclaim.  ``can_admit``/``can_grow`` and
        the allocation paths all use this, so caching a prefix never
        shrinks the pool the scheduler believes it has."""
        return self.allocator.n_free + self.reclaimable_blocks

    def _alloc(self, n: int) -> List[int]:
        """Allocate with trie reclaim: under pool pressure, LRU cached
        prefixes are evicted back to the free list first."""
        short = n - self.allocator.n_free
        if short > 0:
            evicted = self.prefix_trie.evict(short, self.allocator.refcount)
            if evicted:
                self.prefix_evictions += len(evicted)
                self.allocator.free(evicted)
        return self.allocator.alloc(n)

    def _blocks_needed(self, prompt_len: int,
                       chunk_size: Optional[int] = None) -> int:
        """Admission cost, capped at a slot's worst case — the ONE
        accounting rule shared by free-now and could-ever admission
        checks.

        One-shot (``chunk_size=None``): the whole prompt's block cover
        plus one decode block, all allocated up front.  Chunked: only
        the FIRST chunk's cover — later chunks (and the decode block)
        allocate incrementally as the scheduler serves them."""
        if chunk_size is not None:
            return min(self.blocks_for(min(prompt_len, chunk_size)),
                       self.max_blocks_per_slot)
        return min(self.blocks_for(prompt_len) + 1, self.max_blocks_per_slot)

    def can_admit(self, prompt_len: int,
                  chunk_size: Optional[int] = None) -> bool:
        """Enough free blocks to START serving the prompt now: its full
        cover plus one decode block one-shot, or just the first chunk's
        cover under chunked admission."""
        if not self.any_paged:
            return True
        return self._effective_free() >= self._blocks_needed(prompt_len,
                                                             chunk_size)

    def can_ever_admit(self, prompt_len: int,
                       chunk_size: Optional[int] = None) -> bool:
        """Whether the prompt could be SERVED with EVERY block free —
        False means the engine would MemoryError once it reaches the
        queue head; long-lived frontends reject at submit instead.

        One-shot admission needs the whole cover plus a decode block in
        one allocation.  Chunked admission allocates incrementally, so
        the bound is only the final residency — the cover of the prompt
        plus its first decode write (``blocks_for(prompt_len + 1)``),
        one block less than one-shot whenever the prompt is not
        block-aligned.  Attention still reads ALL prior positions, so
        chunking relaxes the allocation granularity, never the peak."""
        if not self.any_paged:
            return True
        if chunk_size is not None:
            return self.allocator.num_blocks >= min(
                self.blocks_for(prompt_len + 1), self.max_blocks_per_slot)
        return self.allocator.num_blocks >= self._blocks_needed(prompt_len)

    def prefill_len(self, prompt_len: int) -> int:
        """Padded cache length a prefill should build for this prompt.

        Paged: the block-aligned prompt cover (so prefill leaves reshape
        straight into pool blocks).  Dense: the full max_len (seed
        behaviour).
        """
        if not self.any_paged:
            return self.max_len
        return self.blocks_for(prompt_len) * self.block_size

    # -- slot lifecycle ------------------------------------------------------
    def alloc_blocks(self, slot: int, prompt_len: int):
        """Allocate the prompt's block cover for ``slot`` ahead of a
        paged-native prefill (``api.serve_prefill_paged`` scatters the
        prompt K/V straight into these blocks on device)."""
        assert not self.slot_blocks[slot], (slot, self.slot_blocks[slot])
        nb = self.blocks_for(prompt_len)
        self.slot_blocks[slot] = self._alloc(nb) if nb else []
        self._map(self.slot_blocks[slot])
        return self.slot_blocks[slot]

    def install_prefill(self, slot: int, new_pools, dense_leaves) -> None:
        """Adopt the pools returned by a paged-native prefill — the
        prompt K/V is already scattered into ``slot``'s blocks on device
        (no host round-trip of a dense cache) — and copy the non-paged
        leaves (ring buffers, recurrent state, cross-attn K/V) into the
        slot's dense row."""
        for j, m in enumerate(self.paged_mask):
            if m:
                self.pools[j] = new_pools[j]
            else:
                self.denses[j] = self.denses[j].at[:, slot].set(
                    dense_leaves[j][:, 0].astype(self.denses[j].dtype))

    def admit(self, slot: int, cache1_leaves, prompt_len: int) -> None:
        """Write a B=1 prefill cache (built at ``prefill_len``) into
        ``slot``: paged leaves scatter into freshly-allocated pool blocks,
        dense leaves copy into the slot row.  This is the host-side
        fallback for layouts with no paged leaves (``kv_layout='dense'``,
        hybrid/ssm/windowed configs); paged admission goes through
        ``alloc_blocks`` + ``api.serve_prefill_paged`` +
        ``install_prefill`` and never round-trips the cache."""
        assert not self.slot_blocks[slot], (slot, self.slot_blocks[slot])
        nb = self.blocks_for(prompt_len)
        blocks = self._alloc(nb) if nb else []
        self.slot_blocks[slot] = blocks
        self._map(blocks)
        bs = self.block_size
        for j, (m, leaf) in enumerate(zip(self.paged_mask, cache1_leaves)):
            if m:
                view = leaf[:, 0, :nb * bs]                   # (L, nb*bs, ...)
                blk = view.reshape(view.shape[0], nb, bs, *view.shape[2:])
                self.pools[j] = self.pools[j].at[:, np.asarray(blocks)].set(
                    blk.astype(self.pools[j].dtype))
            else:
                self.denses[j] = self.denses[j].at[:, slot].set(
                    leaf[:, 0].astype(self.denses[j].dtype))

    # -- copy-on-write -------------------------------------------------------
    def _cow(self, slot: int, k: int) -> None:
        """``slot`` is about to WRITE into its k-th table block; if that
        block is shared (refcount > 1: a sibling slot's table or the
        prefix trie also maps it) copy it into a fresh block and repoint
        this slot's table row first.  The copy MUST happen host-side
        before dispatch — the jitted steps donate the pools and scatter
        in place, so inside the jit there is no "before"."""
        old = self.slot_blocks[slot][k]
        if self.allocator.refcount(old) <= 1:
            return
        new = self._alloc(1)[0]
        for j, m in enumerate(self.paged_mask):
            if m:
                self.pools[j] = self.pools[j].at[:, new].set(
                    self.pools[j][:, old])
        self.slot_blocks[slot][k] = new
        self._unmap([old])
        self._map([new])
        self.allocator.free([old])
        self.cow_copies += 1

    def _cow_range(self, slot: int, start: int, end: int):
        """Table indices of ``slot``'s EXISTING blocks covering write
        positions [start, end] (inclusive)."""
        if not self.any_paged or end < start or not self.slot_blocks[slot]:
            return range(0)
        bs = self.block_size
        return range(max(start // bs, 0),
                     min(end // bs, len(self.slot_blocks[slot]) - 1) + 1)

    def cow_for_write(self, slot: int, start: int, end: int) -> None:
        """COW every shared block of ``slot`` covering write positions
        [start, end] — the guard every write path runs before its
        dispatch (one-shot prefill scatter, chunk rows, decode /
        speculative / multi-step windows)."""
        for k in self._cow_range(slot, start, end):
            self._cow(slot, k)

    def ensure_capacity(self, slot: int, pos: int,
                        write_start: Optional[int] = None) -> bool:
        """Make sure ``slot`` owns the block covering write index ``pos``
        — and, because the caller is about to WRITE positions
        [write_start, pos] (default: just ``pos``), that none of the
        covering blocks is shared: shared ones are COW-copied here.
        Returns False when the pool can't supply the growth plus the
        copies (caller defers or preempts); never raises mid-write."""
        if not self.any_paged:
            return True
        need = pos // self.block_size + 1
        have = len(self.slot_blocks[slot])
        start = pos if write_start is None else write_start
        cow = [k for k in self._cow_range(slot, start, pos)
               if self.allocator.refcount(self.slot_blocks[slot][k]) > 1]
        if self._effective_free() < max(0, need - have) + len(cow):
            return False
        if need > have:
            grown = self._alloc(need - have)
            self.slot_blocks[slot].extend(grown)
            self._map(grown)
        for k in cow:
            self._cow(slot, k)
        return True

    def can_grow(self, slot: int, pos: int,
                 write_start: Optional[int] = None) -> bool:
        """Whether ``ensure_capacity(slot, pos, write_start)`` would
        succeed right now, WITHOUT allocating — the engine sizes a
        speculative draft window to the free pool instead of preempting
        a neighbour just to speculate."""
        if not self.any_paged:
            return True
        need = pos // self.block_size + 1
        start = pos if write_start is None else write_start
        n_cow = sum(1 for k in self._cow_range(slot, start, pos)
                    if self.allocator.refcount(self.slot_blocks[slot][k]) > 1)
        grow = max(0, need - len(self.slot_blocks[slot]))
        return self._effective_free() >= grow + n_cow

    def rewind(self, slot: int, pos: int) -> None:
        """Shrink ``slot``'s block table to the cover of write index
        ``pos`` — the speculative-decode rewind.  A draft window writes
        K/V up to ``pos + K``; when only part of the window is accepted
        the engine just decrements the slot's position (the
        ``kv_pos <= positions[b]`` masks already make the stale rows
        invisible, and the next step overwrites them) and returns any
        block now WHOLLY past the cover to the free list.  O(blocks
        freed) — at most ceil(K / block_size) per step.

        COW interaction: the next write lands at ``pos``, so if a
        sibling adopted the covering block while this slot decoded ahead
        of it, the block is copied here — rewinding never scribbles over
        a shared prefix."""
        if not self.any_paged:
            return
        keep = pos // self.block_size + 1
        extra = self.slot_blocks[slot][keep:]
        if extra:
            del self.slot_blocks[slot][keep:]
            self._unmap(extra)
            self.allocator.free(extra)
        if self.slot_blocks[slot]:
            self._cow(slot, min(keep, len(self.slot_blocks[slot])) - 1)

    # -- prefix cache --------------------------------------------------------
    def match_prefix(self, tokens) -> tuple:
        """Longest cached whole-block run for ``tokens`` —
        ``([], 0)`` on layouts with nothing paged."""
        if not self.any_paged:
            return [], 0
        return self.prefix_trie.match_prefix(tokens)

    def adopt_prefix(self, slot: int, tokens) -> int:
        """Map the longest cached run into ``slot``'s (empty) block
        table: each matched block gains a reference and becomes the
        slot's table row for its positions.  Returns the matched token
        count — the suffix boundary chunked prefill starts at."""
        assert not self.slot_blocks[slot], (slot, self.slot_blocks[slot])
        blocks, hit_len = self.match_prefix(tokens)
        if blocks:
            self.allocator.incref(blocks)
            self.slot_blocks[slot] = list(blocks)
            self._map(blocks)
        return hit_len

    def release(self, slot: int,
                publish_tokens: Optional[np.ndarray] = None) -> None:
        """Drop ``slot``'s block references.  With ``publish_tokens``
        (the token history the slot's K/V actually covers) the
        FULL-BLOCK prefix run is published into the prefix trie instead
        of freed: the slot's references transfer to the trie (duplicates
        of already-cached runs are dropped), so a later request with the
        same prefix maps the blocks straight into its table and prefills
        only its suffix."""
        blocks = self.slot_blocks[slot]
        self.slot_blocks[slot] = []
        self._unmap(blocks)
        if publish_tokens is not None and self.any_paged and blocks:
            nb = min(len(publish_tokens) // self.block_size, len(blocks))
            if nb:
                _, dupes = self.prefix_trie.publish(
                    publish_tokens[:nb * self.block_size], blocks[:nb])
                self.allocator.free(dupes)
            self.allocator.free(blocks[nb:])
        else:
            self.allocator.free(blocks)

    # -- ragged batch views --------------------------------------------------
    def block_table(self, idxs, positions, *,
                    pad_pow2: bool = True) -> Optional[np.ndarray]:
        """(B, nb_max) int32 table where row r covers positions
        [0, positions[r]] for slot ``idxs[r]`` — rows may sit at
        DIFFERENT positions (ragged fused decode).  A scalar
        ``positions`` broadcasts to every row.

        Rows shorter than the widest are padded with their own first
        block, and ``pad_pow2`` pads the column count to the next power
        of two the same way, so decode compiles O(log max_blocks) shapes;
        every padded column sits past its row's ``positions[r]`` and the
        per-row kv_pos<=pos mask discards it.
        """
        if not self.any_paged:
            return None
        positions = np.broadcast_to(
            np.asarray(positions, np.int64).reshape(-1), (len(idxs),))
        nbs = positions // self.block_size + 1
        nb_max = int(nbs.max())
        if pad_pow2:
            nb_max = pow2(nb_max)
        rows = []
        for i, nb_i in zip(idxs, nbs):
            own = self.slot_blocks[i][:int(nb_i)]
            rows.append(own + [own[0]] * (nb_max - len(own)))
        return np.asarray(rows, np.int32)

    def dense_sub(self, idxs):
        """Batch-row slices of the dense leaves (None where paged)."""
        sel = np.asarray(idxs)
        return [None if d is None else d[:, sel] for d in self.denses]

    def write_back(self, idxs, new_pools, new_denses) -> None:
        sel = np.asarray(idxs)
        for j, (np_, nd) in enumerate(zip(new_pools, new_denses)):
            if np_ is not None:
                self.pools[j] = np_
            if nd is not None:
                self.denses[j] = self.denses[j].at[:, sel].set(nd)
