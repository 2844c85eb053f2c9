"""The traffic generator: one closed loop of ``lanes`` clients.

A mix file gives the prompt and output length distributions and the prompt
bucket. The backlog is built in ``BACKLOG_WAVES`` waves of ``lanes``
requests. Each wave draws its lengths stratified from the seed: the i-th
of ``lanes`` lengths is the distribution's quantile at (i + u) / lanes,
with u uniform in [0, 1) and drawn anew for every length, so every wave
spans the whole distribution while every seed serves other lengths. The
seed also orders the lengths within a wave, pairs prompts with outputs,
and draws the token ids. A mix that states ``length_seed`` draws its
lengths, their order and their pairing from that seed instead, so every
run serves the same lengths in the same order and the seed draws only the
token ids: its window then holds the same work on every seed, where a
few long admissions in a window would otherwise set the rate. Every
arrival is at 0: a lane frees when a
request completes, and the engine admits the next request of the FIFO
backlog into it, so the k-th completion releases the request at queue
position ``lanes + k - 1``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np

# waves of ``lanes`` requests in a backlog: more than a window completes
# in any cell (the drive raises if the backlog drains)
BACKLOG_WAVES = 64


@dataclasses.dataclass
class Planned:
    uid: int
    tokens: np.ndarray    # (prompt,) int32
    max_new: int          # tokens served, the first (from prefill) included


def stratified(dist: dict, n: int, rng: np.random.Generator) -> List[int]:
    """``n`` lengths of a length distribution, one from each of its ``n``
    equal-probability strata, ascending, each rounded up to whole tokens
    (so a length lies in (lo, hi]: no bucket holds the lower edge alone)."""
    (kind, (lo, hi)), = dist.items()
    if kind != "log_uniform":
        raise ValueError(f"unknown length distribution {kind!r}")
    u = (np.arange(n) + rng.random(n)) / n
    return [int(math.ceil(math.exp(math.log(lo) + x * (math.log(hi) - math.log(lo)))))
            for x in u]


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any seed >= 0."""
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def closed_loop(mix: dict, lanes: int, seed: int, vocab: int) -> List[Planned]:
    """The backlog of one run, in queue order (uid = queue position)."""
    rng = seed_rng(seed, 1)
    sizes = (rng if "length_seed" not in mix
             else seed_rng(int(mix["length_seed"]), 2))
    out: List[Planned] = []
    for _ in range(BACKLOG_WAVES):
        p = sizes.permutation(stratified(mix["prompt_tokens"], lanes, sizes))
        o = sizes.permutation(stratified(mix["output_tokens"], lanes, sizes))
        for pl, ol in zip(p, o):
            toks = rng.integers(0, vocab, size=(int(pl),), dtype=np.int32)
            out.append(Planned(uid=len(out), tokens=toks, max_new=int(ol)))
    return out
