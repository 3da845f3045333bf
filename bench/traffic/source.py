"""What a traffic generator kind hands the harness.

A kind module defines ``make_source(params, seed, vocab, max_len)``
returning an object with:

  due(now) -> list[Turn]     turns due by ``now`` (seconds since the
                             traffic started), each returned once
  next_due() -> float|None   the earliest still-pending due time
  on_done(turn, served, now) a turn's reply is complete: ``served`` is
                             every token the engine produced for it
  history() -> iterator     endless transcripts (np.ndarray) the
                             traffic would have left in the prefix
                             cache before the run; the harness fills
                             the page pool with them in set-up

Open-loop kinds fix due times up front; closed-loop and multi-turn
kinds schedule the next turn from ``on_done``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Turn:
    due: float                 # seconds since the traffic started
    prompt: np.ndarray         # (n,) int32: everything the client sends
    out_tokens: int            # tokens to generate, the first included
    stream: int = 0            # the client or conversation it belongs to
    index: int = 0             # its position in that stream
    key: Optional[int] = None  # set by the harness
