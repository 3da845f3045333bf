"""Closed-loop multi-turn chat over a stateless API.

``clients`` users each run one chat session after another.  A session
picks a system prompt from a shared pool by Zipf popularity, then runs
a number of turns.  Every turn sends the WHOLE transcript (system
prompt, every earlier user message and every reply the engine produced)
plus a new user message, as an OpenAI-style stateless client does; the
next turn is due one think time after the reply completes.  A session
ends after its drawn turn count, or earlier when the next turn would
pass ``context_cap`` tokens; its user starts the next session one think
time later.  User ``i`` starts at ``i * stagger_s / clients``.

``history()`` replays earlier sessions from the same distributions: the
whole transcript each left behind (seeded tokens stand in for the
replies), for the harness to fill the prefix cache with before the
traffic starts, as a server that has been running holds them.

Parameters (traffic file): clients, stagger_s, system_prompts {count,
length, popularity}, turns, user_tokens, output_tokens, think_s,
context_cap, each a distribution as ``bench.draw`` reads it.
"""
from __future__ import annotations

import heapq
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from bench import draw
from bench.traffic.source import Turn


class _Draws:
    """One family of per-session streams; ``prefix`` names the family so
    the replayed history never shifts the live traffic's draws."""

    def __init__(self, p: Dict, seed: int, prefix: str, strata: int):
        def stream(dist, name, integer):
            return draw.Stream(dist, draw.rng_for(seed, prefix + name),
                               integer=integer, block=strata)
        self.pick = stream(p["system_prompts"]["popularity"], "popularity",
                           True)
        self.turns = stream(p["turns"], "turns", True)
        self.user = stream(p["user_tokens"], "user_tokens", True)
        self.out = stream(p["output_tokens"], "output_tokens", True)
        self.think = stream(p["think_s"], "think", False)
        self.text = draw.rng_for(seed, prefix + "user_text")


class Sessions:
    def __init__(self, p: Dict, seed: int, vocab: int, max_len: int):
        self.p, self.seed, self.vocab = p, seed, vocab
        self.strata = int(p.get("strata", draw.STRATA))
        # the transcript plus its reply must fit the engine's context
        self.cap = min(int(p["context_cap"]), max_len - 2)
        sp = p["system_prompts"]
        # which popularity rank gets which length is the same for every
        # seed: it sets how long the typical context is
        lens = draw.stratified(sp["length"], sp["count"],
                               draw.rng_for(0, "system_lengths"))
        tok_rng = draw.rng_for(seed, "system_prompts")
        self.system = [draw.tokens(tok_rng, vocab, round(n)) for n in lens]
        self.d = _Draws(p, seed, "", self.strata)
        n = int(p["clients"])
        # session id -> (client, transcript so far, turns left)
        self.live: Dict[int, Tuple[int, np.ndarray, int]] = {}
        self.pending: List[Tuple[float, int, Turn]] = []   # heap by due
        self._seq = 0
        self.next_session = 0
        for c in range(n):
            self._start(c, float(p.get("stagger_s", 0.0)) * c / n)

    def _push(self, turn: Turn) -> None:
        heapq.heappush(self.pending, (turn.due, self._seq, turn))
        self._seq += 1

    def _start(self, client: int, due: float) -> None:
        sid = self.next_session
        self.next_session += 1
        self.live[sid] = (client, self.system[self.d.pick.next()],
                          self.d.turns.next())
        self._turn(sid, due, 0)

    def _turn(self, sid: int, due: float, index: int) -> None:
        """Queue the session's next turn, or end it and start its
        client's next session one think time later."""
        client, transcript, left = self.live[sid]
        user = self.d.user.next()
        out = self.d.out.next()
        if left <= 0 or len(transcript) + user + out > self.cap:
            del self.live[sid]
            self._start(client, due + (self.d.think.next() if index else 0))
            return
        prompt = np.concatenate(
            [transcript, draw.tokens(self.d.text, self.vocab, user)])
        self.live[sid] = (client, prompt, left - 1)
        self._push(Turn(due=due, prompt=prompt, out_tokens=out, stream=sid,
                        index=index))

    def due(self, now: float) -> List[Turn]:
        out = []
        while self.pending and self.pending[0][0] <= now:
            out.append(heapq.heappop(self.pending)[2])
        return out

    def next_due(self) -> Optional[float]:
        return self.pending[0][0] if self.pending else None

    def on_done(self, turn: Turn, served: List[int], now: float) -> None:
        sid = turn.stream
        client, prompt, left = self.live[sid]
        self.live[sid] = (client, np.concatenate(
            [prompt, np.asarray(served, np.int32)]), left)
        self._turn(sid, now + self.d.think.next(), turn.index + 1)

    def history(self) -> Iterator[np.ndarray]:
        """Transcripts of earlier sessions, endlessly, each as its last
        turn left it: its prompt followed by its reply."""
        d = _Draws(self.p, self.seed, "history.", self.strata)
        while True:
            ctx = self.system[d.pick.next()]
            for _ in range(d.turns.next()):
                user, out = d.user.next(), d.out.next()
                if len(ctx) + user + out > self.cap:
                    break
                ctx = np.concatenate(
                    [ctx, draw.tokens(d.text, self.vocab, user + out)])
            yield ctx


def make_source(params: Dict, seed: int, vocab: int, max_len: int):
    return Sessions(params, seed, vocab, max_len)
