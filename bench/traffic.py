"""The one traffic generator.  A mix is a data file, ``bench/traffic/<mix>.json``.

A mix is a backlog of ``n_requests`` waiting at the start (``loop``
``saturated``); the engine admits as slots and pages free up.  Lengths
are lognormal, given by median and sigma and clipped to ``[min, max]``:
each block of ``block`` requests takes the lognormal quantiles at
(i + 0.5) / block, permuted (prompt and output lengths separately) by the
mix's own ``order_seed``.  So every run's seed gets the same sequence of
sizes and does the same work; the seed draws the prompt tokens (uniform
over the vocabulary), as it draws the weights.  A run admits only a
prefix of the backlog, so a permutation per seed would change the work
with the seed.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

BENCH = Path(__file__).resolve().parent
LOOPS = ("saturated",)


@dataclass
class Planned:
    prompt: np.ndarray          # (S,) int32
    max_new: int


def load_traffic(name: str, data: Path = BENCH) -> dict:
    mix = json.loads((data / "traffic" / f"{name}.json").read_text())
    if mix["loop"] not in LOOPS:
        raise ValueError(f"traffic {name}: loop {mix['loop']!r} not in {LOOPS}")
    return mix


def lognormal_grid(dist: dict, n: int) -> np.ndarray:
    """The n lognormal quantiles at (i + 0.5) / n, clipped, as integers."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def generate(mix: dict, vocab: int, seed: int) -> list:
    """The backlog of one run, in submission order."""
    rng = np.random.default_rng(int(seed))
    order = np.random.default_rng(int(mix["order_seed"]))
    n, block = int(mix["n_requests"]), int(mix["block"])
    n_blocks = -(-n // block)
    p_grid = lognormal_grid(mix["prompt"], block)
    o_grid = lognormal_grid(mix["output"], block)
    prompts = np.concatenate([order.permutation(p_grid) for _ in range(n_blocks)])[:n]
    outputs = np.concatenate([order.permutation(o_grid) for _ in range(n_blocks)])[:n]
    return [Planned(rng.integers(0, vocab, int(s), dtype=np.int32), int(o))
            for s, o in zip(prompts, outputs)]
