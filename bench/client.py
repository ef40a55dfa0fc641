"""The client side of ``LLM``: sends a schedule on time and stamps every
token as it is delivered.

One thread drives ``LLM`` the way the facade runs without its pump:
``submit`` each request once it is due, then advance the engine one
iteration (``engine.step()``), and again.  Tokens reach ``on_chunk`` as
the engine delivers them, which only appends a stamp.  A request that
falls due during a step is sent when the step ends; latency is taken
from when it was due, not from when it was sent, so every stall counts
against every request that waited behind it.

(With the pump thread running, a second thread calling ``submit``
waits on the facade's lock, which the pump releases and takes again at
once: on a TPU v5e such a sender ran 10-36 s late, so an open loop could
not offer its rate.)
"""
from __future__ import annotations

import dataclasses
import heapq
import time
from typing import Dict, List, Optional

import numpy as np

POLL_S = 0.002
KV_SAMPLE_S = 0.05


@dataclasses.dataclass
class Rec:
    due: float                       # absolute perf_counter seconds
    prompt: np.ndarray
    max_new: int
    sent: Optional[float] = None
    rid: Optional[int] = None
    req: object = None               # the engine's Request
    refused: Optional[str] = None
    session: Optional[int] = None
    turn: int = 0


class Client:
    def __init__(self, llm, params_for):
        self.llm = llm
        self.params_for = params_for     # max_new -> SamplingParams
        self.recs: List[Rec] = []
        self.stamps: Dict[int, list] = {}  # rid -> [(t, token, finish)]
        self.kv_samples: List[int] = []
        llm.engine.add_consumer(self.on_chunk)

    def on_chunk(self, chunk) -> None:
        self.stamps.setdefault(chunk.rid, []).append(
            (time.perf_counter(), chunk.token, chunk.finish_reason))

    # -- sending ------------------------------------------------------------
    def send(self, rec: Rec) -> Rec:
        rec.sent = time.perf_counter()
        try:
            rec.req = self.llm.submit(rec.prompt, self.params_for(rec.max_new))
            rec.rid = rec.req.rid
        except ValueError as e:          # refused at the door
            rec.refused = str(e)
        self.recs.append(rec)
        return rec

    def finished(self, rec: Rec) -> Optional[float]:
        """When the request's last token arrived, or None."""
        if rec.refused is not None:
            return rec.sent
        st = self.stamps.get(rec.rid)
        if st and st[-1][2] is not None:
            return st[-1][0]
        return None

    def generated(self, rec: Rec) -> List[int]:
        return [tok for _, tok, _ in self.stamps.get(rec.rid, [])]

    def advance(self) -> bool:
        """One engine iteration, if there is work; False when idle."""
        engine = self.llm.engine
        if not engine.has_work:
            return False
        engine.step()
        return True

    def _sample_kv(self, state) -> None:
        now = time.perf_counter()
        if state["sample_kv"] and now >= state["next_kv"]:
            self.kv_samples.append(self.llm.kv_usage()["blocks_in_use"])
            state["next_kv"] = now + KV_SAMPLE_S

    # -- the three kinds of traffic -------------------------------------------
    def run(self, schedule, t0: float, stop: float, hooks=()) -> None:
        """Send ``schedule`` (times relative to ``t0``) until ``stop``.
        ``hooks`` are ``(time, fn)`` pairs called once their time has come
        (opening the window, starting and stopping a trace)."""
        hooks = sorted(hooks, key=lambda h: h[0])
        state = {"sample_kv": False, "next_kv": 0.0}
        heap: list = []                   # (due, order, Rec)
        order = 0

        def push(rec):
            nonlocal order
            heapq.heappush(heap, (rec.due, order, rec))
            order += 1

        kind = schedule.kind
        waiting: list = []    # sent, unfinished: their end sends the next
        if kind == "open":
            for it in schedule.items:
                push(Rec(t0 + it.due_s, it.prompt, it.max_new))
        elif kind == "closed":
            pending = iter(schedule.items)
            for _ in range(schedule.clients):
                it = next(pending)
                push(Rec(t0, it.prompt, it.max_new))
        else:
            history: Dict[int, np.ndarray] = {}
            for s, it in enumerate(schedule.items):
                first = it.turns[0]
                prompt = np.concatenate([schedule.systems[it.system],
                                         first.user])
                history[s] = prompt
                push(Rec(t0 + it.due_s, prompt, first.answer_len, session=s))
        while True:
            now = time.perf_counter()
            while hooks and hooks[0][0] <= now:
                hooks.pop(0)[1](state)
            while heap and heap[0][0] <= min(now, stop):
                waiting.append(self.send(heapq.heappop(heap)[2]))
            if now >= stop:
                break
            still = []
            for rec in waiting:
                end = self.finished(rec)
                if end is None:
                    still.append(rec)
                    continue
                if kind == "closed":
                    it = next(pending, None)
                    if it is not None:
                        push(Rec(end, it.prompt, it.max_new))
                elif kind == "sessions":
                    item = schedule.items[rec.session]
                    nxt = rec.turn + 1
                    if rec.refused is None and nxt < len(item.turns):
                        turn = item.turns[nxt]
                        hist = np.concatenate([
                            history[rec.session],
                            np.asarray(self.generated(rec), np.int32),
                            turn.user])
                        history[rec.session] = hist
                        if len(hist) + turn.answer_len \
                                <= self.llm.engine.max_len:
                            push(Rec(end + turn.think_s, hist,
                                     turn.answer_len, session=rec.session,
                                     turn=nxt))
            waiting = still
            self._sample_kv(state)
            if self.advance():
                continue
            nxt_t = min([heap[0][0]] if heap else [], default=now + POLL_S)
            if hooks:
                nxt_t = min(nxt_t, hooks[0][0])
            time.sleep(max(0.0, min(nxt_t - time.perf_counter(), POLL_S)))
        while hooks:
            hooks.pop(0)[1](state)

    def drain(self, due_lo: float, due_hi: float, deadline: float) -> None:
        """Wait until every request due in ``[due_lo, due_hi)`` has its
        first token (or was refused), or ``deadline`` passes."""
        while time.perf_counter() < deadline:
            if all(r.refused is not None or self.stamps.get(r.rid)
                   for r in self.recs if due_lo <= r.due < due_hi):
                return
            if not self.advance():
                return
