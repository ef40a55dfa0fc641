"""Warm the step programs a cell's window will run, before its pre-roll.

The serving engine compiles one fused step program per batch bucket ×
query width × block-table width × count of rows that take a token
(each a power of two), so a window that meets a new combination stops
every request for a trace, a lower and a compile or a load from the
persistent cache.  The pre-roll of the cell's own traffic meets the
common combinations; the rare ones (final chunks narrower than
``chunk_size``, a batch of many prompts mid-prefill) would land in the
window at random, more in one seed than another.

This drives the engine through the public ``LLM.submit``, the engine's
step and ``cancel`` alone, with throw-away requests whose lengths pick
each combination in turn, kept out of the prefix cache, so that the
pool is empty again when it ends.  The traffic file's ``warm`` entry
names what it covers: ``rows`` (the least and most requests in flight
in the window), ``context`` (the least and most positions its longest
request reaches) and ``heads`` (fractions of a full batch that take a
token while the rest are mid-prompt).  One long request per table width
prefills first, in a small batch.  Then, from the widest table down,
with the longest request left holding the table at its width: for each
batch bucket, short requests fill the batch to just over its lower
half, and one request per query width (1, 2, 4, ..., ``chunk_size``)
and one of two chunks each take a step; then, for each ``heads``
fraction, the batch is filled to its bucket with two-chunk requests
while only that fraction of it takes a token; the long request is
cancelled after.
"""
from __future__ import annotations

import math
from typing import List

import numpy as np


def pow2(n: int) -> int:
    return 1 << max(0, math.ceil(math.log2(max(1, n))))


def buckets(lo: int, hi: int) -> List[int]:
    """The powers of two from ``pow2(lo)`` to ``pow2(hi)``."""
    out, b = [], pow2(lo)
    while b <= pow2(hi):
        out.append(b)
        b *= 2
    return out


def plan(spec: dict, engine: dict) -> dict:
    """The combinations ``warm`` visits: batch buckets, table widths (in
    blocks), query widths, and the fractions of a batch whose rows take
    a token while the others are mid-prompt."""
    bs, chunk = engine["block_size"], engine["chunk_size"]
    rows_lo, rows_hi = spec["rows"]
    ctx_lo, ctx_hi = spec["context"]
    return {"batches": buckets(rows_lo, min(rows_hi, engine["n_slots"])),
            "tables": buckets(ctx_lo // bs + 1, ctx_hi // bs + 1),
            "widths": buckets(1, chunk),
            "heads": spec.get("heads", [])}


def warm(llm, spec: dict, engine: dict, vocab: int) -> int:
    """Run the warm-up; returns the number of engine steps it took.
    Every request it sent is finished or cancelled when it returns."""
    from repro.serve.params import SamplingParams

    rng = np.random.default_rng(13)       # the same work for every seed
    eng = llm.engine
    bs, chunk = engine["block_size"], engine["chunk_size"]
    p = plan(spec, engine)
    live: list = []
    steps = 0

    def send(n: int, max_new: int):
        req = llm.submit(rng.integers(0, vocab, n, dtype=np.int32),
                         SamplingParams(max_new_tokens=max_new,
                                        prefix_cache=False))
        live.append(req)
        return req

    def step():
        nonlocal steps
        eng.step()
        steps += 1
        live[:] = [r for r in live if not r.done]

    def settle():
        """Step until every live request has its first token (no prompt
        row left), so the next step's width is the probe's alone."""
        while any(not r.generated for r in live):
            step()

    # one long request per table width, each with its last position in
    # the upper half of its table; they prefill together in a small
    # batch, and the longest left sets the width while its probes run
    rounds = len(p["tables"]) * len(p["batches"]) * (
        len(p["widths"]) + 4 + 3 * len(p["heads"]))
    keep = 2 * ((p["tables"][-1] // 2) * bs // chunk + 1 + rounds)
    holds = []
    for nb in p["tables"]:
        length = (nb // 2) * bs + 1
        if nb >= 2 and length + keep < min(nb * bs, engine["max_len"]):
            holds.append(send(length, keep))
    settle()
    fillers: list = []
    while holds:
        for b in p["batches"]:
            want = max(0, b // 2 + 1 - len(holds))  # rows besides holds
            while len(fillers) > want:
                eng.cancel(fillers.pop())
            while len(fillers) < want:
                fillers.append(send(1, keep))
            settle()
            for w in p["widths"]:
                send(w, 1)                 # one final chunk of width w
                step()
            step()                         # decode rows alone (T = 1)
            send(chunk + 1, 1)             # a chunk mid-prompt, then one
            settle()
            for frac in p["heads"]:
                # a full batch in which only ``b * frac`` rows take a
                # token and the rest are mid-prompt (as when many
                # prompts arrive together)
                g = max(1, int(b * frac))
                if g < len(holds) or b > engine["n_slots"]:
                    continue
                while len(fillers) > g - len(holds):
                    eng.cancel(fillers.pop())
                while len(fillers) < g - len(holds):
                    fillers.append(send(1, keep))
                for _ in range(b - g):     # (in the same step)
                    send(chunk + 1, 1)
                settle()
        eng.cancel(holds.pop())
    for r in list(live):
        eng.cancel(r)
    while eng.has_work:
        step()
    return steps
