"""A cell's requests, drawn from the seed: lengths, prompts, arrivals.

Every seed gets the same multiset of sizes and of inter-arrival gaps, in
another order.  Each distribution is read at the quantile points
``(i + 0.5) / n``, and the output lengths are paired with the prompt
lengths once, for every seed alike.  The pairs, ordered by prompt length
(or by output length, where the cell's ``strata_by`` says
``output_len``), are cut into ``strata`` equal strata, and each block of
``strata`` consecutive requests takes one pair of every stratum, so any
stretch of the stream of a block or more has the whole spread of
lengths.  The seed picks the order and the prompts' tokens.  A closed
loop of ``c`` clients stratified by output length in blocks of ``c``
starts every seed with the same spread of answers, so the requests that
finish in a short window, and the refills they bring, vary little from
seed to seed.  A cell with an ``order_seed`` draws the order (and the
arrival gaps' order) from it instead, the same for every run: where a
window holds a few dozen requests of an open loop, the order alone moves
the tails of the first-token time by a quarter, and the run's seed then
draws only the prompts' tokens (and the weights).

Lengths are lognormal (``median``, ``sigma``), clipped to ``[min, max]``.
An output is cut to ``max_seq - prompt`` so that every request ends at
its own length: the engine stops a request whose cache is full.  With a
``prefix`` section, each prompt starts with one of ``count`` seeded
shared prefixes of ``tokens`` tokens (Zipf ``zipf_a`` popularity) and
``prompt_len`` is the length of the rest.
"""
from __future__ import annotations

import dataclasses
import statistics

import numpy as np

#: which output length goes with which prompt length: one fixed pairing
PAIRING_SEED = 0


@dataclasses.dataclass
class Planned:
    rid: int
    prompt: np.ndarray          # (len,) int32
    max_new: int
    gap_s: float | None         # open loop: wait after the previous arrival


def points(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def stratified(values: np.ndarray, strata: int, rng) -> np.ndarray:
    """``values`` (in stratum order, a multiple of ``strata`` long)
    reordered so that each consecutive block of ``strata`` holds one value
    of every stratum, in random order."""
    n = len(values)
    if n % strata:
        raise ValueError(f"{n} requests are not a multiple of {strata} "
                         f"strata")
    blocks = n // strata
    cols = np.stack([rng.permutation(values[j * blocks:(j + 1) * blocks])
                     for j in range(strata)], axis=1)    # (blocks, strata)
    for row in cols:
        rng.shuffle(row)
    return cols.reshape(-1)


def lengths(spec: dict, n: int) -> np.ndarray:
    """Ascending lengths of ``spec`` at the ``n`` quantile points."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([statistics.NormalDist().inv_cdf(u) for u in points(n)])
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def zipf_index(count: int, a: float, n: int) -> np.ndarray:
    """Ascending prefix indices at the quantile points of Zipf(a) over
    ``count`` items (rank 1 most popular)."""
    p = np.arange(1, count + 1, dtype=np.float64) ** -a
    cdf = np.cumsum(p / p.sum())
    return np.minimum(np.searchsorted(cdf, points(n)), count - 1)


def plan(cell: dict, config: dict, seed: int, generator) -> list[Planned]:
    n, k = cell["requests"], cell["strata"]
    vocab = config["vocab_size"]
    max_seq = config["engine"]["max_seq"]
    streams = np.random.SeedSequence(int(seed)).spawn(4)
    rng_len, rng_tok, rng_arr, rng_pre = map(np.random.default_rng, streams)
    if "order_seed" in cell:
        # the cell fixes its schedule; the run's seed draws only tokens
        rng_len, rng_arr, rng_pre = map(np.random.default_rng,
                                        np.random.SeedSequence(
                                            cell["order_seed"]).spawn(3))
    # (prompt, output) pairs fixed for the cell, whatever the seed: the
    # output cap below depends on the pair, and every seed serves the same
    # multiset of pairs in another order
    pair = np.random.default_rng(PAIRING_SEED).permutation(n)
    user = lengths(cell["prompt_len"], n)
    out = lengths(cell["output_len"], n)[pair]
    key = {"prompt_len": user, "output_len": out}[cell.get("strata_by",
                                                           "prompt_len")]
    order = stratified(np.argsort(key, kind="stable"), k, rng_len)
    user, out = user[order], out[order]
    pre = cell.get("prefix")
    if pre:
        pool = [rng_tok.integers(0, vocab, pre["tokens"]).astype(np.int32)
                for _ in range(pre["count"])]
        which = stratified(zipf_index(pre["count"], pre["zipf_a"], n), k,
                           rng_pre)
    gaps = None
    if not generator.CLOSED:
        gaps = stratified(np.sort(generator.gaps(cell["arrivals"],
                                                 points(n))), k, rng_arr)
    reqs = []
    for i in range(n):
        body = rng_tok.integers(0, vocab, int(user[i])).astype(np.int32)
        prompt = np.concatenate([pool[which[i]], body]) if pre else body
        if len(prompt) > max_seq - 2:
            raise ValueError(f"prompt of {len(prompt)} tokens does not fit "
                             f"max_seq {max_seq}")
        reqs.append(Planned(
            rid=i, prompt=prompt,
            max_new=int(min(out[i], max_seq - len(prompt))),
            gap_s=None if gaps is None else float(gaps[i])))
    return reqs
