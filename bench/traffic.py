"""One generator for every traffic mix; a mix is a data file of
parameters under ``traffic/``.

Every seed gets the same work in another order.  Each quantity (gaps
between arrivals, prompt and output lengths, turns, think times,
system-prompt picks) is a stratified draw: the ``n`` values at the
midpoints ``(i + 0.5) / n`` of the distribution's quantiles.  They are
paired into requests (or sessions) by one fixed shuffle, the same for
every seed; the seed shuffles the order of the requests and of the gaps
between arrivals, and draws the token ids.  So two seeds differ in order
and content, not in how much there is to do.

Kinds:
  ``open``      independent requests, Poisson arrivals at ``rate_per_s``;
                the pre-roll and the window each draw their own stratified
                set, the pre-roll at ``preroll_rate_per_s`` (default the
                same rate), so every window holds the same requests;
  ``sessions``  Poisson session arrivals at ``session_rate_per_s``; each
                session picks one of a few shared system prompts (Zipf
                popularity), then sends turns, each the whole history plus
                a new user message, due a think time after the previous
                answer ended;
  ``closed``    ``clients`` callers, each sending its next request when
                its last one completes.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import List, Optional

import numpy as np

PAIRING = 20211221      # the one shuffle that pairs sizes into requests
TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load(name: str) -> dict:
    spec = json.loads((TRAFFIC_DIR / f"{name}.json").read_text())
    if spec.get("kind") not in ("open", "sessions", "closed"):
        raise ValueError(f"traffic {name}: unknown kind {spec.get('kind')!r}")
    return spec


def _mid(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The ``n`` stratified draws of a length or time distribution."""
    u = _mid(n)
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] + 1 - dist["min"])
    elif kind == "exponential":
        return -dist["mean"] * np.log1p(-u)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return np.clip(np.floor(x), dist["min"], dist["max"]).astype(np.int64)


def zipf_picks(count: int, s: float, n: int) -> np.ndarray:
    """Stratified picks among ``count`` items with Zipf(s) popularity."""
    w = 1.0 / np.arange(1, count + 1) ** s
    cdf = np.cumsum(w / w.sum())
    return np.minimum(np.searchsorted(cdf, _mid(n)), count - 1)


def _arrivals(rate: float, n: int, rng) -> np.ndarray:
    """``n`` arrival times whose gaps are the stratified exponential
    draws at mean ``1 / rate``, shuffled; the last lands at ``n / rate``."""
    gaps = rng.permutation(-np.log1p(-_mid(n)))
    gaps *= (n / rate) / gaps.sum()
    return np.cumsum(gaps) - gaps[0]


def _open_items(spec: dict, rate: float, span: float, offset: float, rng,
                tokens) -> list:
    """Independent requests arriving at ``rate`` in ``[offset, offset +
    span)``: the stratified sizes of ``ceil(rate * span)`` requests, their
    gaps scaled so that the last arrives inside the span."""
    fixed = np.random.default_rng(PAIRING)
    n = max(1, math.ceil(rate * span))
    due = offset + _arrivals(rate, n, rng) * (span / (n / rate))
    plen = fixed.permutation(quantiles(spec["prompt"], n))
    olen = fixed.permutation(quantiles(spec["output"], n))
    order = rng.permutation(n)
    return [Item(float(t), tokens(plen[i]), int(olen[i]))
            for t, i in zip(due, order)]


@dataclasses.dataclass
class Turn:
    user: np.ndarray            # new user tokens of this turn
    answer_len: int             # tokens asked of the model
    think_s: float              # wait after the previous answer ends


@dataclasses.dataclass
class Item:
    """One request (``open``, ``closed``) or one session (``sessions``)."""
    due_s: float                # first due time, from the schedule's start
    prompt: Optional[np.ndarray] = None
    max_new: int = 0
    system: Optional[int] = None
    turns: List[Turn] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Schedule:
    kind: str
    items: List[Item]
    systems: List[np.ndarray]   # shared system prompts (``sessions``)
    clients: int = 0            # ``closed``


def generate(spec: dict, seed: int, seconds: float, vocab: int) -> Schedule:
    """The schedule of one run: ``preroll_s`` of set-up traffic, then a
    window of ``seconds``, from the seed."""
    rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), 7])
    fixed = np.random.default_rng(PAIRING)
    span = spec.get("preroll_s", 0.0) + seconds

    def tokens(n):
        return rng.integers(0, vocab, int(n), dtype=np.int32)

    kind = spec["kind"]
    if kind == "open":
        pre = spec.get("preroll_s", 0.0)
        items = []
        if pre > 0:
            items = _open_items(spec, spec.get("preroll_rate_per_s",
                                               spec["rate_per_s"]),
                                pre, 0.0, rng, tokens)
        items += _open_items(spec, spec["rate_per_s"], seconds, pre, rng,
                             tokens)
        return Schedule(kind, items, [])
    if kind == "closed":
        n = spec["requests"]
        plen = fixed.permutation(quantiles(spec["prompt"], n))
        olen = fixed.permutation(quantiles(spec["output"], n))
        items = [Item(0.0, tokens(plen[i]), int(olen[i]))
                 for i in rng.permutation(n)]
        return Schedule(kind, items, [], clients=spec["clients"])
    sp = spec["system_prompts"]
    systems = [tokens(sp["length"]) for _ in range(sp["count"])]
    n = max(1, math.ceil(spec["session_rate_per_s"] * span))
    due = _arrivals(spec["session_rate_per_s"], n, rng)
    picks = fixed.permutation(zipf_picks(sp["count"], sp["zipf_s"], n))
    nturns = fixed.permutation(quantiles(dict(spec["turns"], dist="uniform"),
                                         n))
    total = int(nturns.sum())
    user = fixed.permutation(quantiles(dict(spec["user"], dist="uniform"),
                                       total))
    answer = fixed.permutation(quantiles(dict(spec["answer"],
                                              dist="uniform"), total))
    think = fixed.permutation(quantiles(
        {"dist": "exponential", "mean": spec["think_s_mean"]}, total))
    first = np.concatenate([[0], np.cumsum(nturns)[:-1]])
    items = []
    for t, i in zip(due, rng.permutation(n)):
        k = int(first[i])
        turns = [Turn(tokens(user[k + j]), int(answer[k + j]),
                      float(think[k + j])) for j in range(int(nturns[i]))]
        items.append(Item(float(t), system=int(picks[i]), turns=turns))
    return Schedule(kind, items, systems)
