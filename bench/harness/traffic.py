"""Seeded open-loop traffic on the wall clock.

After the program's ``serve/frontend/traffic.py`` (seeded Poisson arrivals,
lengths from finite choice sets), with arrivals in seconds from the start
of the measured window instead of scheduler steps.  A traffic file gives:

- ``process``: ``"poisson"``, at ``rate_per_s``;
- ``prompt_lens``/``prompt_weights``, ``output_lens``/``output_weights``;
- ``warmup_max_new``: the decode budget of set-up's warm-up requests.

The mix is stratified, so that seeds change which request is which and
not how much work the window holds.  Every seed gets the same number of
requests, the same multiset of gaps between arrivals (the exponential
distribution's quantiles) and the same multiset of lengths.  The
schedule is laid out in blocks: each block of ``GAP_BLOCK`` arrivals
holds one gap from each of that many quantile strata, and each block of
lengths holds the weights' shares as whole counts (20 requests for
weights 0.4/0.35/0.25, 2 for 0.5/0.5).  Each block is shuffled on its
own.  So the part of the schedule that a window above the fleet's
capacity gets through has the same mix for every seed, and no seed puts
all its long gaps at the start.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

#: arrivals per block of gaps; one from each of this many quantile strata
GAP_BLOCK = 8
#: the largest block of lengths; weights that need more are rounded over
#: the whole window instead
MAX_LEN_BLOCK = 64


@dataclasses.dataclass(frozen=True)
class Arrival:
    idx: int
    due_s: float
    tokens: np.ndarray          # (1, S) int32
    max_new: int


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def _counts(n: int, weights) -> List[int]:
    """``n`` split by the weights' shares, rounded by largest remainder."""
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    raw = n * w
    c = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - c), kind="stable")[:n - c.sum()]:
        c[i] += 1
    return c.tolist()


def length_block(weights) -> int:
    """The fewest requests in which every weight's share is a whole count,
    or 0 where that takes more than ``MAX_LEN_BLOCK``."""
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    for b in range(1, MAX_LEN_BLOCK + 1):
        if np.allclose(w * b, np.round(w * b), rtol=0, atol=1e-9):
            return b
    return 0


def _stratified(rng, n: int, values, weights) -> List[int]:
    b = length_block(weights) or n
    out: List[int] = []
    while len(out) < n:
        k = min(b, n - len(out))
        blk = []
        for v, c in zip(values, _counts(k, weights)):
            blk += [int(v)] * c
        rng.shuffle(blk)
        out += blk
    return out


def _due_times(traffic: dict, rng, seconds: float) -> List[float]:
    if traffic["process"] != "poisson":
        raise ValueError(f"unknown arrival process {traffic['process']!r}")
    rate = float(traffic["rate_per_s"])
    if rate <= 0:
        raise ValueError(f"rate_per_s must be positive, got {rate}")
    n = int(round(rate * seconds))
    q = (np.arange(n) + 0.5) / max(n, 1)
    gaps = -np.log1p(-q) / rate               # ascending quantiles
    nb = -(-n // GAP_BLOCK)
    blocks = [gaps[j::nb] for j in range(nb)]  # one per stratum each
    for blk in blocks:
        rng.shuffle(blk)
    order = rng.permutation(nb)
    gaps = np.concatenate([blocks[j] for j in order]) if n else gaps
    due = np.cumsum(gaps) - gaps               # the first is due at 0
    return [float(t) for t in due if t < seconds]


def arrivals(traffic: dict, seed: int, seconds: float,
             vocab: int) -> List[Arrival]:
    """The window's arrivals, sorted by due time: a pure function of
    (traffic, seed, seconds, vocab)."""
    due = _due_times(traffic, _rng(seed, 0), seconds)
    n = len(due)
    lens = _stratified(_rng(seed, 1), n, traffic["prompt_lens"],
                       traffic["prompt_weights"])
    outs = _stratified(_rng(seed, 2), n, traffic["output_lens"],
                       traffic["output_weights"])
    return _build(_rng(seed, 3), due, lens, outs, vocab)


def warmup(traffic: dict, seed: int, vocab: int,
           at_least: int = 1) -> List[Arrival]:
    """Set-up's warm-up: every prompt length with every decode budget
    (capped at ``warmup_max_new``), repeated until there are ``at_least``
    requests, all due at once, drawn from a stream the window never
    uses."""
    cap = int(traffic.get("warmup_max_new") or max(traffic["output_lens"]))
    pairs = [(int(p), min(int(o), cap)) for p in traffic["prompt_lens"]
             for o in sorted(set(traffic["output_lens"]))]
    pairs = list(dict.fromkeys(pairs))
    pairs = pairs * -(-at_least // len(pairs))
    return _build(_rng(seed, 99), [0.0] * len(pairs),
                  [p for p, _ in pairs], [o for _, o in pairs], vocab)


def _build(rng, due, lens, outs, vocab) -> List[Arrival]:
    out = []
    for i, (t, S, m) in enumerate(zip(due, lens, outs)):
        toks = rng.integers(0, vocab, S, dtype=np.int64)
        out.append(Arrival(idx=i, due_s=float(t),
                           tokens=toks.astype(np.int32)[None], max_new=m))
    out.sort(key=lambda a: (a.due_s, a.idx))
    return out
