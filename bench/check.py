"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample
of the requests the window finished, drawn from the seed, is run
through the plain reference: each prompt followed by the tokens the
engine served for it.  Every served token is greedy, so it should be
the reference's best token at its position up to rounding; the number
compared is the widest gap by which a served token's reference logit
lies below the reference's best at that position.

The control (``control_gap``) puts the reference itself, computed one
precision step down, in the program's place: at each position of the
same prompts and tokens it reads the gap of the token the lower
precision puts first.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench import draw

MIN_PAD = 512


def pad_len(n: int) -> int:
    """Contexts pad to a power of two (at least ``MIN_PAD``) so a run
    compiles the reference for a handful of shapes."""
    p = MIN_PAD
    while p < n:
        p *= 2
    return p


def rows_len(n: int) -> int:
    p = 8
    while p < n:
        p *= 2
    return p


def sample(done: Sequence[Dict], seed: int, count: int,
           must: Sequence[int] = ()) -> List[Dict]:
    """``count`` finished requests drawn from the seed, always with the
    longest context and the indices in ``must`` among them."""
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: len(done[i]["prompt"])
                  + len(done[i]["served"]))
    picked = [longest] + [i for i in must if i != longest]
    rest = [i for i in range(len(done)) if i not in picked]
    rng = draw.rng_for(seed, "check_sample")
    extra = max(0, count - len(picked))
    if rest and extra:
        picked += [rest[i] for i in
                   rng.choice(len(rest), min(extra, len(rest)),
                              replace=False)]
    return [done[i] for i in picked]


def _context(req: Dict) -> Tuple[np.ndarray, np.ndarray, int]:
    prompt = np.asarray(req["prompt"], np.int32)
    served = np.asarray(req["served"], np.int32)
    ctx = np.concatenate([prompt, served[:-1]])
    rows = len(prompt) - 1 + np.arange(len(served))
    return ctx, rows, len(served)


def _padded(ctx: np.ndarray, rows: np.ndarray):
    toks = np.zeros(pad_len(len(ctx)), np.int32)
    toks[:len(ctx)] = ctx
    r = np.full(rows_len(len(rows)), rows[-1], np.int32)
    r[:len(rows)] = rows
    return toks, r


def gaps(ref, hf: Dict, arch: Dict, weights, reqs: Sequence[Dict],
         control: Optional[str] = None
         ) -> Tuple[float, Optional[float], int]:
    """(widest gap of a served token below the float32 reference's best,
    the same for the token the ``control`` precision puts first at each
    position (None without a control), tokens compared)."""
    widest, ctrl, n = 0.0, None, 0
    for req in reqs:
        ctx, rows, m = _context(req)
        toks, r = _padded(ctx, rows)
        lg = ref.logits(hf, arch, weights, toks, r)[:m]
        best = lg.max(axis=1)
        served = np.asarray(req["served"], np.int64)
        widest = max(widest, float((best - lg[np.arange(m), served]).max()))
        if control is not None:
            low = ref.logits(hf, arch, weights, toks, r, quantize=control)[:m]
            pick = low[:, :lg.shape[1]].argmax(axis=1)
            g = float((best - lg[np.arange(m), pick]).max())
            ctrl = g if ctrl is None else max(ctrl, g)
        n += m
    return widest, ctrl, n
