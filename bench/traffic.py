"""The traffic generator: one general generator, driven by a mix's file.

A mix (``traffic/<name>.json``) gives the loop kind, the length
distributions and, for an open loop, the arrival process; ``engine``
holds the engine settings the mix is sized for.

Lengths: every block of ``block`` consecutive requests holds the same
multisets of prompt and of output lengths, the distributions' quantiles
at (i + 0.5) / block, in one scrambled layout that is the same for every
seed; ``--seed`` permutes them within each run of ``REORDER``
consecutive requests and draws the token ids.  So every seed asks for the
same work, and each burst of the open loop's fixed arrivals carries the
same work, while which request of a run gets which length, and every
token, changes with the seed.

Arrivals (open loop): a two-state on/off modulated Poisson process, ON at
``base * burst_factor`` and OFF at ``base / burst_factor`` with
exponential dwell times (the arithmetic of ``repro.serving.workload``'s
``_arrivals``, copied here so the yardstick stays put).  ``base`` is set
so the long-run mean is ``rate_req_s``.  The arrival times come from the
mix's own ``arrival_seed``: they are the same for every ``--seed``.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

from .model import seed_words

REORDER = 4  # the seed permutes lengths within runs of this many requests
LAYOUT = 1  # seeds the block's scrambled layout, the same for every seed


def quantile(dist: dict, u: float) -> int:
    """Length at quantile ``u`` of a length distribution."""
    lo, hi = int(dist["lo"]), int(dist["hi"])
    if dist["dist"] == "uniform":
        return min(hi, lo + int(u * (hi - lo + 1)))
    if dist["dist"] == "lognormal":
        z = NormalDist().inv_cdf(u)
        x = math.exp(math.log(dist["median"]) + dist["sigma"] * z)
        return int(min(hi, max(lo, round(x))))
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


def mean_length(dist: dict, block: int) -> float:
    return sum(quantile(dist, (i + 0.5) / block)
               for i in range(block)) / block


def base_rate(mix: dict) -> float:
    """ON/OFF base rate whose time-weighted mean is ``rate_req_s``."""
    bf, on, off = mix["burst_factor"], mix["on_s"], mix["off_s"]
    return mix["rate_req_s"] * (on + off) / (on * bf + off / bf)


def arrival_times(mix: dict, horizon_s: float) -> list[float]:
    """On/off modulated Poisson arrivals in [0, horizon_s)."""
    rng = np.random.default_rng(mix["arrival_seed"])
    rate0, bf = base_rate(mix), mix["burst_factor"]
    times: list[float] = []
    now, on = 0.0, True
    phase_end = rng.exponential(mix["on_s"])
    while True:
        rate = rate0 * (bf if on else 1.0 / bf)
        gap = rng.exponential(1.0 / rate)
        if now + gap > phase_end and bf != 1.0:
            now = phase_end
            on = not on
            phase_end = now + rng.exponential(mix["on_s"] if on
                                              else mix["off_s"])
            continue
        now += gap
        if now >= horizon_s:
            return times
        times.append(now)


class Mix:
    """The requests of one run: request k's lengths and tokens, and (open
    loop) the due times, all from the mix and ``seed``."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.seed, self.vocab = mix, seed, vocab
        self.block = int(mix["block"])
        self._blocks: dict[int, list[tuple[int, int]]] = {}
        self._words = [int(w) for w in seed_words(seed)]

    def lengths(self, k: int) -> tuple[int, int]:
        b, i = divmod(k, self.block)
        if b not in self._blocks:
            n = self.block
            prompts = [quantile(self.mix["prompt"], (j + 0.5) / n)
                       for j in range(n)]
            outs = [quantile(self.mix["output"], (j + 0.5) / n)
                    for j in range(n)]
            # the fixed layout, then the seed's permutation within each
            # run; prompt and output lengths are laid out independently
            fixed = np.random.default_rng([LAYOUT, b])
            p, o = fixed.permutation(n), fixed.permutation(n)
            rng = np.random.default_rng(self._words + [b])
            for lo in range(0, n, REORDER):
                p[lo:lo + REORDER] = rng.permutation(p[lo:lo + REORDER])
                o[lo:lo + REORDER] = rng.permutation(o[lo:lo + REORDER])
            self._blocks[b] = [(prompts[p[j]], outs[o[j]]) for j in range(n)]
        return self._blocks[b][i]

    def request(self, k: int) -> tuple[list[int], int]:
        n_prompt, n_out = self.lengths(k)
        rng = np.random.default_rng(self._words + [1 << 30, k])
        return rng.integers(0, self.vocab, n_prompt).tolist(), n_out

    def due_times(self, seconds: float) -> list[float]:
        """Open loop: due times relative to the window's start; those
        before 0 fall in the pre-roll, which is part of set-up."""
        pre = float(self.mix.get("preroll_s", 0.0))
        return [t - pre for t in arrival_times(self.mix, pre + seconds)]
