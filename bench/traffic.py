"""The one traffic generator: every mix is a data file under ``traffic/``.

Two kinds of mix, named by the file's ``driver``:

* ``train`` — seeded synthetic token rows, ``rows_per_chip`` x
  ``seq_len`` per step. Token ids follow a Zipf law over the vocabulary,
  as text does, so that the loss moves within a few steps.
* ``serve_closed`` — a closed loop of ``clients`` clients. Every round of
  ``clients`` requests holds the same sizes: the ``clients`` stratified
  quantiles of the prompt and output length distributions. The event loop
  group deals client ``c`` to loop ``c % event_loops``; the sizes are dealt
  to the loops by rank in the same way, so every loop gets the same share
  of short and long requests in every round. The seed pairs each loop's
  prompts with its outputs, orders them and draws the tokens: every seed
  asks for the same work in another order, and each loop's wave has the
  same longest prompt (its prefill shape) and longest output (its number
  of decode steps) in every round.

Seeds may exceed 32 bits; they enter numpy's generator as 32-bit words.
"""
from __future__ import annotations

import math
import statistics

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(
        [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, *stream])


# -- training -----------------------------------------------------------

def train_batch(mix: dict, vocab: int, seed: int, step: int,
                rows: int) -> dict:
    """``rows`` token rows for ``step``: {"tokens", "labels"} (int32),
    labels shifted by one."""
    rng = rng_for(seed, 1, step)
    toks = (rng.zipf(mix["zipf_a"], size=(rows, mix["seq_len"] + 1)) - 1) \
        % vocab
    toks = toks.astype(np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


# -- serving ------------------------------------------------------------

def quantiles(dist: dict, n: int) -> list:
    """``n`` stratified quantiles of a clipped lognormal, as ints,
    ascending."""
    nd = statistics.NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        x = dist["median"] * math.exp(dist["sigma"] * z)
        out.append(int(round(min(max(x, dist["min"]), dist["max"]))))
    return out


def loop_sizes(mix: dict) -> list:
    """Per event loop, the (prompt lengths, output lengths) that it serves
    in every round: the stratified quantiles dealt by rank."""
    n, loops = mix["clients"], mix["serve"]["event_loops"]
    prompts, outputs = quantiles(mix["prompt"], n), quantiles(mix["output"], n)
    return [(prompts[l::loops], outputs[l::loops]) for l in range(loops)]


def round_requests(mix: dict, vocab: int, seed: int, rnd: int) -> list:
    """Round ``rnd``'s requests: [(prompt int32 array, max_new)] in client
    order; client ``c`` goes to loop ``c % event_loops``."""
    rng = rng_for(seed, 2, rnd)
    per_loop = [(rng.permutation(p), rng.permutation(o))
                for p, o in loop_sizes(mix)]
    reqs = []
    for k in range(len(per_loop[0][0])):
        for p, o in per_loop:
            reqs.append((rng.integers(0, vocab, size=int(p[k]),
                                      dtype=np.int32), int(o[k])))
    return reqs


def warmup_requests(mix: dict, vocab: int, seed: int) -> list:
    """One round per distinct longest prompt of a loop's wave, ``clients``
    prompts of that length with ``warmup_new`` tokens each: every prefill
    shape the window meets (a wave pads its prompts to its longest), from
    a stream the window never draws."""
    longest = sorted({max(p) for p, _ in loop_sizes(mix)})
    rng = rng_for(seed, 3)
    return [[(rng.integers(0, vocab, size=length, dtype=np.int32),
              mix["warmup_new"]) for _ in range(mix["clients"])]
            for length in longest]
