"""One general generator for every traffic mix, read from a cell's
``traffic`` parameters; every draw comes from ``--seed``.

Serving mixes are built so that the seed changes the order of the work
and not its amount: prompt and output lengths are stratified quantiles
of their lognormals, gaps between arrivals stratified quantiles of the
exponential, users a fixed count per share. The seed permutes each list
and draws the token ids, so every seed offers the same load.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Any, Dict, Iterator, List, Optional

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A numpy generator for one named stream of one seed (any size)."""
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


def seed32(seed: int, stream: int = 0) -> int:
    """A 31-bit integer key for JAX, derived from a seed of any size."""
    return int(rng(seed, 0x5EED, stream).integers(0, 2**31 - 1))


# ---------------------------------------------------------------------------
# training batches


def train_batch(traffic: Dict[str, Any], vocab: int, seed: int,
                step: int) -> Dict[str, np.ndarray]:
    """Step ``step``'s batch: random token rows, every row and step new.

    ``task`` "lm": next-token targets over the rows; "cls": one label of
    ``n_classes`` per row."""
    b, s = traffic["batch"], traffic["seq"]
    g = rng(seed, 1, step)
    if traffic["task"] == "lm":
        toks = g.integers(0, vocab, (b, s + 1), dtype=np.int64)
        return {"tokens": toks[:, :-1].astype(np.int32),
                "targets": toks[:, 1:].astype(np.int32)}
    if traffic["task"] == "cls":
        return {"tokens": g.integers(0, vocab, (b, s)).astype(np.int32),
                "label": g.integers(0, traffic["n_classes"],
                                    (b,)).astype(np.int32)}
    raise ValueError(f"unknown training task {traffic['task']!r}")


def train_batches(traffic, vocab: int, seed: int,
                  first_step: int = 0) -> Iterator[Dict[str, np.ndarray]]:
    step = first_step
    while True:
        yield train_batch(traffic, vocab, seed, step)
        step += 1


# ---------------------------------------------------------------------------
# serving requests


@dataclasses.dataclass(frozen=True)
class Arrival:
    due_s: float                 # seconds after the window opens
    prompt: np.ndarray           # (P,) int32
    max_new: int
    user: str


def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` stratified quantiles of a lognormal with ``median`` and
    ``sigma``, clipped to [min, max]."""
    nd = NormalDist()
    q = np.array([nd.inv_cdf(p) for p in _strata(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * q)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def arrivals(traffic: Dict[str, Any], vocab: int, seed: int,
             seconds: float, rate: Optional[float] = None) -> List[Arrival]:
    """The open-loop schedule of one window: Poisson arrivals at
    ``rate`` (default the cell's ``rate_rps``), each request's lengths
    and user drawn from the cell's fixed multiset."""
    rate = traffic["rate_rps"] if rate is None else rate
    n = max(1, int(round(rate * seconds)))
    g = rng(seed, 2)
    gaps = -np.log1p(-_strata(n)) / rate          # exponential quantiles
    order = g.permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(order)[:-1]])
    plens = g.permutation(lognormal_lengths(traffic["prompt"], n))
    olens = g.permutation(lognormal_lengths(traffic["output"], n))
    users: List[str] = []
    for u in traffic["users"]:
        users += [u["name"]] * int(round(u["share"] * n))
    users = (users + [traffic["users"][0]["name"]] * n)[:n]
    users = list(g.permutation(users))
    out = []
    for i in range(n):
        prompt = g.integers(0, vocab, int(plens[i])).astype(np.int32)
        out.append(Arrival(float(due[i]), prompt, int(olens[i]),
                           str(users[i])))
    return out


# ---------------------------------------------------------------------------
# adapters: a user's fine-tune as replay-log records


def adapter_records(adapter: Dict[str, Any], seed: int,
                    user_index: int) -> List[Dict[str, Any]]:
    """``records`` replay-log records of one user's ZO fine-tune at the
    cell's lr/eps: a fresh direction seed per step and a projected
    gradient drawn from N(0, gs_sigma)."""
    g = rng(seed, 3, user_index)
    n = adapter["records"]
    seeds = g.integers(0, 2**32, n, dtype=np.uint64)
    gs = g.normal(0.0, adapter["gs_sigma"], n).astype(np.float32)
    return [{"step": i, "seed": int(seeds[i]), "gs": [float(gs[i])],
             "lr": float(adapter["lr"]), "eps": float(adapter["eps"])}
            for i in range(n)]
