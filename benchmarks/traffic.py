"""The one general traffic generator: it reads a mix's data file under
``traffic/`` and makes, from ``--seed``, what the runner feeds the system.

Two kinds of mix:

``train``     batches of token ids, ``batch_per_chip`` x chips rows of
              ``seq_len``; the target of a row is the row shifted by one.
``requests``  generation requests.  The mix fixes a SET of sizes (prompt
              and answer lengths taken at evenly spaced quantiles of the
              stated distributions, so it is the same for every seed);
              the seed orders them, pairs them, draws the token ids and
              deals them to the clients of a closed loop.  So two seeds
              do the same work in another order.  An open loop (arrivals
              at a rate, bursts) comes with the cell that first needs it.

Either kind may state ``windows`` (a whole number from 1 up, default 1):
how many times the command's ``--seconds`` the cell's one window lasts,
for a mix whose rate over one follows the order of its sizes.
"""

from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, Iterator, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    path = os.path.join(HERE, "traffic", f"{name}.json")
    with open(path) as f:
        return check_mix(json.load(f), path)


def check_mix(mix: dict, path: str) -> dict:
    if mix.get("kind") not in ("train", "requests"):
        raise ValueError(f"{path}: kind must be 'train' or 'requests'")
    if mix["kind"] == "requests" and mix.get("loop") != "closed":
        raise ValueError(f"{path}: loop must be 'closed'")
    windows = mix.get("windows", 1)
    if type(windows) is not int or windows < 1:
        raise ValueError(f"{path}: windows must be a whole number from 1 up, "
                         f"not {windows!r}")
    return mix


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


# -- training batches ---------------------------------------------------------

def train_batches(mix: dict, seed: int, chips: int, vocab: int) -> Iterator:
    """Endless (tokens, targets) int32 batches; every row differs."""
    if mix.get("ids", "uniform") != "uniform":
        raise ValueError("only uniform token ids are implemented")
    rows, seq = int(mix["batch_per_chip"]) * chips, int(mix["seq_len"])
    rng = _rng(seed, 1)
    while True:
        ids = rng.integers(0, vocab, (rows, seq + 1), dtype=np.int32)
        yield (np.ascontiguousarray(ids[:, :-1]),
               np.ascontiguousarray(ids[:, 1:]))


# -- requests -------------------------------------------------------------------

@dataclass(frozen=True)
class Request:
    index: int
    prompt: np.ndarray
    max_new_tokens: int
    temperature: float
    top_k: int
    seed: int

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


def quantile_sizes(dist: dict, n: int) -> List[int]:
    """``n`` whole sizes at the evenly spaced quantiles of ``dist``."""
    lo, hi = int(dist["min"]), int(dist["max"])
    qs = [(i + 0.5) / n for i in range(n)]
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    mu, sigma = math.log(float(dist["median"])), float(dist["sigma"])
    raw = [math.exp(mu + sigma * NormalDist().inv_cdf(q)) for q in qs]
    return [min(hi, max(lo, int(round(x)))) for x in raw]


def _sampling_plan(mix: dict, n: int) -> List[dict]:
    """The sampling settings of the ``n`` sizes, by the mix's shares."""
    plan: List[dict] = []
    for entry in mix["sampling"]:
        plan += [entry] * int(round(float(entry["share"]) * n))
    if len(plan) != n:
        raise ValueError(f"sampling shares do not make {n} requests")
    return plan


def size_sets(mix: dict):
    """(n, prompt lengths, answer lengths): the mix's fixed set of sizes."""
    n = int(mix["n_sizes"])
    return (n, quantile_sizes(mix["prompt_len"], n),
            quantile_sizes(mix["answer_len"], n))


def request_stream(mix: dict, seed: int, vocab: int) -> Iterator[Request]:
    """The mix's requests in the seed's order, round after round."""
    n, prompts, answers = size_sets(mix)
    plan = _sampling_plan(mix, n)
    cap = int(mix.get("max_total_tokens", 0))
    rng = _rng(seed, 2)
    index = 0
    while True:
        p_order, a_order, s_order = (rng.permutation(n) for _ in range(3))
        for j in range(n):
            p = prompts[p_order[j]]
            a = answers[a_order[j]]
            if cap:
                a = max(1, min(a, cap - p))
            s = plan[s_order[j]]
            yield Request(
                index=index,
                prompt=rng.integers(0, vocab, p, dtype=np.int32),
                max_new_tokens=a, temperature=float(s["temperature"]),
                top_k=int(s.get("top_k", 0)),
                seed=int(rng.integers(0, 2 ** 32 - 1)))
            index += 1


def describe_requests(mix: dict) -> Dict[str, float]:
    """What the mix's fixed set of sizes looks like (printed by a run)."""
    n, prompts, answers = size_sets(mix)
    return {"n_sizes": n, "prompt_median": float(np.median(prompts)),
            "prompt_max": max(prompts), "answer_mean": float(np.mean(answers)),
            "answer_max": max(answers)}
