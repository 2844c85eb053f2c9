"""The output comparison that decides ``correct``.

Once the window has closed and the program's cache is freed, a sample of
the served requests, drawn from the seed and always holding the one served
the most tokens, is run through the float32 reference over its prompt and
its served tokens. For every served token the gap is the reference's best
logit minus the reference's logit of that token, at the position whose
logits chose it (greedy decoding). Three numbers summarise the gaps of the
sample: the widest gap, the mean gap, and the mean gap in doubt: the mean
over the positions where the reference's first choice was in doubt (its
best and second-best logits lie within ``DOUBT`` of each other) or was not
served. The cell's file names the numbers it compares and their limits.

The control (``control=True``, never in a benchmark run) is the same
reference computed with float8 operands: at the same positions of the same
prompts and tokens, the gap of the token the float8 reference puts first.
"""
from __future__ import annotations

import dataclasses
import math
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from chipbench import reference
from chipbench.traffic import seed_rng

# a reference margin (best minus second-best logit) under which a token is
# in doubt: about the logit error of the float8 control, whose widest gap
# reads 0.49-0.97 in qwen1.5-4b-10l.long-decode-8k
DOUBT = 0.5


def mean_gap_in_doubt(gaps: np.ndarray, margins: np.ndarray) -> float:
    """Mean gap over the positions in doubt or missed (every other gap is
    0). Unlike the mean gap it does not shrink with the share of positions
    where one token stands out (as in a greedy loop of random weights),
    where neither a sound path nor a lower precision changes the token."""
    counted = (margins < DOUBT) | (gaps > 0)
    return float(np.sum(gaps) / max(int(np.sum(counted)), 1))


# the numbers a cell can compare, each a summary of the sample's gaps and
# the reference's margins at the same positions
NUMBERS = {"widest_gap": lambda g, m: float(np.max(g)),
           "mean_gap": lambda g, m: float(np.mean(g)),
           "mean_gap_in_doubt": mean_gap_in_doubt}


@dataclasses.dataclass
class Readings:
    gaps: Dict[int, np.ndarray]              # uid -> gap of each served token
    margins: Dict[int, np.ndarray]           # uid -> reference margin there
    control_gaps: Optional[Dict[int, np.ndarray]] = None
    seconds: float = 0.0
    distinct: int = 0                        # distinct served tokens compared

    @property
    def tokens(self) -> int:
        return sum(len(g) for g in self.gaps.values())

    @property
    def requests(self) -> List[int]:
        return list(self.gaps)

    def number(self, name: str, control: bool = False) -> float:
        g = self.control_gaps if control else self.gaps
        if not g:
            return float("nan")
        return NUMBERS[name](np.concatenate(list(g.values())),
                             np.concatenate([self.margins[u] for u in g]))


def pick(served: Dict[int, list], finished: Dict[int, float], rule: dict,
         seed: int) -> List[int]:
    """The sample: the request served the most tokens first, then others
    in an order drawn from the seed, until ``tokens`` served tokens or
    ``max_requests`` requests. ``from`` is "finished" (completed requests
    only) or "served" (any request the drive served a token)."""
    pool = [u for u in served if rule["from"] == "served" or u in finished]
    if not pool:
        return []
    longest = max(pool, key=lambda u: (len(served[u]), -u))
    rest = [u for u in sorted(pool) if u != longest]
    rest = [rest[i] for i in seed_rng(seed, 2).permutation(len(rest))]
    out, total = [longest], len(served[longest])
    for u in rest:
        if total >= rule["tokens"] or len(out) >= rule["max_requests"]:
            break
        out.append(u)
        total += len(served[u])
    return out


def padded_len(n: int, conf: dict, q_chunk: int) -> int:
    m = math.lcm(q_chunk, conf["aqua"]["prefill_q_blk"])
    return -(-n // m) * m


def compare(arch, conf: dict, params, proj, prompts: Dict[int, np.ndarray],
            served: Dict[int, list], sample: List[int], max_seq: int,
            control: bool = False, q_chunk: int = 256) -> Readings:
    t0 = time.perf_counter()
    table = reference.unembed_table(conf, params)
    length = padded_len(max_seq, conf, q_chunk)
    gaps, margins, ctrl, seen = {}, {}, {}, set()
    for uid in sample:
        prompt = np.asarray(prompts[uid], np.int32)
        out = np.asarray(served[uid], np.int32)
        p, n = len(prompt), len(out)
        seq = np.zeros((length,), np.int32)
        seq[:p] = prompt
        seq[p:p + n - 1] = out[:-1]
        pos = np.arange(p - 1, p - 1 + n)
        seen.update(out.tolist())
        h = reference.hidden(arch, conf, params, proj, seq, p,
                             q_chunk=q_chunk)
        gaps[uid], margins[uid] = reference.served_gaps(table, h, pos, out)
        if control:
            hc = reference.hidden(arch, conf, params, proj, seq, p,
                                  quant="fp8", q_chunk=q_chunk)
            choice = reference.control_choice(table, hc, pos, "fp8")
            ctrl[uid], _ = reference.served_gaps(table, h, pos, choice)
    return Readings(gaps=gaps, margins=margins,
                    control_gaps=ctrl if control else None,
                    seconds=time.perf_counter() - t0, distinct=len(seen))


def verdict(readings: Readings, limits: dict, control: bool = False) -> tuple:
    """(correct, {name: {"value", "limit"}}) for the numbers the cell
    compares, of the served path or (``control``) of the control. The
    numbers summarise the whole sample, so a sample that fails counts every
    request in it as failed."""
    shown, ok = {}, readings.tokens > 0
    side = "control " if control else ""
    for name, limit in limits.items():
        value = readings.number(name, control)
        shown[name] = {"value": value, "limit": limit}
        ok = ok and math.isfinite(value) and value <= limit
        print(f"chipbench check: {side}{name} {value!r} limit {limit!r}",
              file=sys.stderr)
    return ok, shown
