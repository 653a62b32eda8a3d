"""The one traffic generator. A traffic mix is a data file
`traffic/<name>.json`; this module turns it and `--seed` into inputs.

The rule that keeps a cell steady: THE FILE FIXES THE WORK, THE SEED ONLY
ORDERS IT. Lengths are a grid of quantiles of the stated distributions (the
same multiset for every seed), arrival gaps are quantiles of the exponential
scaled to the stated rate (same multiset), and the seed shuffles them and
draws the token ids. Two seeds therefore offer the same tokens at the same
mean rate, in another order.

Distributions (`dist`):
  log_uniform  min, max
  log_normal   median, sigma, clipped to [min, max]
each with `quantiles`: how many equally likely values stand for it.
"""
from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_traffic(name: str, root: str = HERE) -> Dict:
    path = os.path.join(root, "traffic", f"{name}.json")
    with open(path) as f:
        spec = json.load(f)
    spec["name"] = name
    return spec


def quantile_values(d: Dict) -> List[int]:
    """The `quantiles` equally likely whole values standing for `d`."""
    n = int(d["quantiles"])
    qs = [(i + 0.5) / n for i in range(n)]
    lo, hi = float(d["min"]), float(d["max"])
    if d["dist"] == "log_uniform":
        vals = [math.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
                for q in qs]
    elif d["dist"] == "log_normal":
        nd = NormalDist()
        vals = [float(d["median"]) * math.exp(float(d["sigma"]) * nd.inv_cdf(q))
                for q in qs]
    else:
        raise ValueError(f"traffic: unknown dist {d['dist']!r}")
    return [int(round(min(hi, max(lo, v)))) for v in vals]


def length_pairs(spec: Dict) -> List[tuple]:
    """One cycle's multiset of (prompt, output) lengths: every pairing of the
    two grids, the output cut where prompt + output would pass `max_total`."""
    cap = int(spec["max_total"])
    pairs = []
    for p in quantile_values(spec["prompt"]):
        for o in quantile_values(spec["output"]):
            pairs.append((p, max(1, min(o, cap - p))))
    return pairs


def gap_values(spec: Dict) -> List[float]:
    """One cycle's multiset of arrival gaps, seconds: quantiles of the
    exponential, scaled so their mean is exactly 1 / rate."""
    n = int(spec["gaps"]["quantiles"])
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    mean = sum(raw) / n
    return [g / mean / float(spec["rate_per_s"]) for g in raw]


@dataclass
class Request:
    prompt: np.ndarray      # (L,) int32 token ids
    max_new_tokens: int
    due_s: float = 0.0      # open loop: offset from the start of arrivals
    full_output: int = 0    # the length before a mid-life cut


def make_requests(spec: Dict, seed: int, vocab: int, count: int,
                  first_wave: int = 0) -> List[Request]:
    """`count` requests: whole cycles of the length multiset, each cycle in
    its own seeded order; for an open loop, due times from whole cycles of
    the gap multiset likewise. The first `first_wave` requests start
    mid-life where the file says so: their output length is cut to a seeded
    uniform fraction of itself."""
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    pairs = length_pairs(spec)
    lens: List[tuple] = []
    while len(lens) < count:
        order = rng.permutation(len(pairs))
        lens.extend(pairs[i] for i in order)
    lens = lens[:count]
    dues = [0.0] * count
    if spec["loop"] == "open_poisson":
        gaps: List[float] = []
        base = gap_values(spec)
        while len(gaps) < count:
            order = rng.permutation(len(base))
            gaps.extend(base[i] for i in order)
        dues = list(np.cumsum(gaps[:count]))
    midlife = bool(spec.get("first_wave", {}).get("midlife"))
    out = []
    for i, ((p, o), due) in enumerate(zip(lens, dues)):
        new = o
        if midlife and i < first_wave:
            new = max(1, int(math.ceil(o * (1.0 - rng.random()))))
        ids = rng.integers(0, vocab, size=p, dtype=np.int32)
        out.append(Request(ids, new, float(due), o))
    return out


def make_train_rows(spec: Dict, seed: int, vocab: int, rows: int, seq: int,
                    classes: int):
    """`rows` full sequences of seeded token ids with a class label for
    every token: x (rows, seq) int32, y (rows, seq, 1) int32. All rows
    differ. The file's `label_shares` fix how many tokens of a row carry
    each class (the same for every seed); the seed places them."""
    if int(spec["sequence_length"]) != seq:
        raise ValueError("traffic and configuration disagree on the"
                         f" sequence length: {spec['sequence_length']} / {seq}")
    shares = [float(s) for s in spec["label_shares"]]
    if len(shares) != classes or abs(sum(shares) - 1.0) > 1e-9:
        raise ValueError(f"traffic: label_shares {shares} do not describe"
                         f" {classes} classes")
    counts = [int(round(s * seq)) for s in shares[1:]]
    row = np.zeros(seq, np.int32)
    row[seq - sum(counts):] = np.repeat(np.arange(1, classes), counts)
    rng = np.random.default_rng([int(seed), 0x7AA1])
    x = rng.integers(0, vocab, size=(rows, seq), dtype=np.int32)
    y = rng.permuted(np.tile(row, (rows, 1)), axis=1)[:, :, None]
    return x, y


def describe(requests: List[Request], spec: Dict) -> Dict:
    """What a run prints about the traffic it generated."""
    p = np.array([len(r.prompt) for r in requests])
    o = np.array([r.max_new_tokens for r in requests])
    q = lambda a: [int(np.percentile(a, x)) for x in (0, 50, 90, 100)]
    out = {"requests": len(requests), "prompt_q0_50_90_100": q(p),
           "output_q0_50_90_100": q(o), "prompt_tokens": int(p.sum()),
           "output_tokens": int(o.sum()), "loop": spec["loop"]}
    if spec["loop"] == "open_poisson":
        out["offered_per_s"] = float(spec["rate_per_s"])
        out["arrivals_span_s"] = float(requests[-1].due_s)
    return out
