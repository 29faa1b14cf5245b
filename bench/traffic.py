"""One generator for every traffic mix: the mix is data, read from
``bench/traffic/<name>.json``.

A serving mix is an open loop: requests arrive on a schedule whether or not
earlier ones have finished.  The schedule is a Poisson process at the
mix's rate, with an arrival at the window's opening, conditioned on
holding ``round(rate * seconds)`` arrivals in the window: given its count,
a Poisson process places its arrivals as independent uniform draws over
the window.  Each request's prompt and output lengths are independent
draws from the mix's distributions.  The path is drawn once, from a fixed
salt, and every seed runs it: the seed draws the token ids (and the
benchmark's weights), so runs of different seeds do the same work at the
same times on other data.  With a few requests to a window, a path drawn
per seed would move the tail latencies more than any change to the
program under test.

A training job is a fixed shape (sequence, batch) fed by the program's own
seeded batch generator; its parameters are read here too.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SCHEDULE_SALT = 20231122      # fixes every mix's sample path


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        mix = json.load(f)
    if mix.get("kind") not in ("serve", "train"):
        raise ValueError(f"traffic {name!r}: kind must be serve or train")
    return mix


def rng(seed: int, *salt: int) -> np.random.Generator:
    """A numpy generator for any whole ``seed``, however large."""
    return np.random.default_rng([seed % (1 << 63), *salt])


def draw(dist: dict, g: np.random.Generator, n: int) -> np.ndarray:
    """``n`` independent draws of ``dist``."""
    kind = dist["dist"]
    if kind == "loguniform":
        return np.exp(g.uniform(math.log(dist["lo"]), math.log(dist["hi"]), n))
    raise ValueError(f"unknown distribution {kind!r}")


@dataclasses.dataclass
class ServeRequest:
    rid: int
    prompt: np.ndarray        # int32 token ids
    max_new: int
    arrival: float            # seconds after the window opens


def serve_requests(mix: dict, seed: int, seconds: float, vocab: int,
                   rate: float | None = None) -> list:
    """The requests due in a window of ``seconds`` at ``rate`` (the mix's
    own rate unless given), in arrival order."""
    rate = mix["rate_per_s"] if rate is None else rate
    n = max(1, int(round(rate * seconds)))
    arrivals = np.concatenate([[0.0], np.sort(
        rng(SCHEDULE_SALT, 0).uniform(0.0, seconds, n - 1))])
    plen = np.floor(draw(mix["prompt_len"], rng(SCHEDULE_SALT, 1), n))
    olen = np.floor(draw(mix["output_len"], rng(SCHEDULE_SALT, 2), n))
    plen, olen = plen.astype(int), olen.astype(int)
    toks = rng(seed, 1).integers(0, vocab, int(plen.sum()), dtype=np.int32)
    offs = np.concatenate([[0], np.cumsum(plen)])
    return [ServeRequest(rid=i, prompt=toks[offs[i]:offs[i + 1]],
                         max_new=int(olen[i]), arrival=float(arrivals[i]))
            for i in range(n)]
