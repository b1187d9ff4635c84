"""One general traffic generator, driven by a mix file (traffic/<mix>.json).

Every seed gets the same work: prompt lengths, output lengths, arrival
gaps, sampled/greedy classes and prefix picks are stratified quantiles of
the mix's distributions (point i of n at u = (i + 0.5) / n), paired into
requests by a fixed shuffle, which the seed only puts in another order.  The seed also draws every token and every
sampling seed.  So two seeds load the chip alike and differ in order and
content, and a run-to-run spread is the system's, not the draw's.

Mix keys (all lengths in tokens):

  loop          "open" (arrivals on a clock) or "closed" (clients that each
                send their next request when the last one finished)
  rate_rps      open loop: mean arrival rate (Poisson: exponential gaps)
  clients       closed loop: number of clients
  lead_s        seconds of traffic before the window opens (part of set-up)
  prompt        {"dist": "uniform"|"pareto", "min", "max"[, "alpha"]};
                with prefixes, the length of the request's own suffix
  output        {"dist": ..., "min", "max"}: tokens each request asks for
  prefixes      optional {"count", "len", "zipf_s"}: shared system prompts,
                one picked per request by Zipf(s) over their rank
  sampled_share share of requests that sample (the rest are greedy)
  sampling      {"temperature", "top_k"} of the sampled requests
  stagger       closed loop: each client's first request asks for a
                stratified share of its drawn output, so completions are
                spread over time from the start (a steady state sooner)
"""
from __future__ import annotations

import dataclasses
import json
import math
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Req:
    """One request as the generator makes it; the harness turns it into a
    program task when it is sent."""
    uid: int
    prompt: np.ndarray          # int32 tokens
    max_new: int
    sampled: bool
    sample_seed: int
    due_s: Optional[float] = None   # open loop: seconds after traffic start
    prefix: Optional[int] = None    # index of its shared prefix, if any


PAIRING_SEED = 0x9A17   # pairs the stratified sizes into requests


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix.get("loop") not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be 'open' or 'closed'")
    return mix


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """n stratified draws of a length distribution, in ascending order.

    pareto: min + floor(Lomax(alpha) * min), capped at max (the long-tail
    shape of serving/loadgen.py, by its inverse CDF)."""
    u = _quantiles(n)
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "uniform":
        out = lo + np.floor(u * (hi - lo + 1))
    elif dist["dist"] == "pareto":
        lomax = (1.0 - u) ** (-1.0 / float(dist["alpha"])) - 1.0
        out = lo + np.floor(lomax * lo)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(out, lo, hi).astype(np.int64)


def phase_count(rate: float, seconds: float) -> int:
    return int(round(rate * seconds))


def zipf_counts(n: int, k: int, s: float) -> np.ndarray:
    """How many of n requests pick each of k prefixes under Zipf(s), by
    largest remainder, so the counts sum to n for every seed."""
    w = 1.0 / np.arange(1, k + 1) ** s
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    order = np.argsort(-(exact - counts), kind="stable")
    counts[order[:n - counts.sum()]] += 1
    return counts


def _exact_share(n: int, share: float, rng) -> np.ndarray:
    flags = np.zeros(n, bool)
    flags[:int(round(n * share))] = True
    return rng.permutation(flags)


class Traffic:
    """The seeded requests of one run.  `open_requests()` gives the whole
    schedule of an open loop; `next_request()` feeds a closed loop."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix = mix
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        self._uid = 0
        pre = mix.get("prefixes")
        self.prefixes: List[np.ndarray] = []
        if pre:
            self.prefixes = [self._tokens(int(pre["len"]))
                             for _ in range(int(pre["count"]))]
        self._block: List[Req] = []

    # -- content ---------------------------------------------------------
    def _tokens(self, n: int) -> np.ndarray:
        return self.rng.integers(0, self.vocab, n, dtype=np.int32)

    def _make_block(self, n: int) -> List[Req]:
        """n requests with the stratified sizes and classes.  The sizes
        are paired into requests by a fixed shuffle, so every seed sends
        the same requests' sizes; the seed orders them."""
        mix, rng = self.mix, self.rng
        fixed = np.random.default_rng(PAIRING_SEED)
        plen = lengths(mix["prompt"], n)
        olen = fixed.permutation(lengths(mix["output"], n))
        sampled = _exact_share(n, float(mix.get("sampled_share", 0.0)),
                               fixed)
        pick = [None] * n
        pre = mix.get("prefixes")
        if pre:
            counts = zipf_counts(n, int(pre["count"]), float(pre["zipf_s"]))
            pick = fixed.permutation(np.repeat(np.arange(len(counts)),
                                               counts))
        order = rng.permutation(n)
        plen, olen, sampled = plen[order], olen[order], sampled[order]
        if pre:
            pick = pick[order]
        seeds = rng.integers(0, 2**31 - 1, n)
        out = []
        for i in range(n):
            prompt = self._tokens(int(plen[i]))
            k = None if pick[i] is None else int(pick[i])
            if k is not None:
                prompt = np.concatenate([self.prefixes[k], prompt])
            out.append(Req(uid=self._uid, prompt=prompt,
                           max_new=int(olen[i]), sampled=bool(sampled[i]),
                           sample_seed=int(seeds[i]), prefix=k))
            self._uid += 1
        return out

    # -- open loop -------------------------------------------------------
    def open_requests(self, phases) -> List[Req]:
        """The schedule of an open loop over consecutive phases (lead-in,
        window, drain), each `phases[i]` seconds long.  A phase holds
        round(rate x length) requests with its own stratified sizes, and
        stratified exponential gaps, permuted and scaled to span the phase:
        every seed sends each phase the same work at the same mean rate."""
        rate = float(self.mix["rate_rps"])
        out, start = [], 0.0
        for length in phases:
            n = phase_count(rate, length)
            if n:
                gaps = self.rng.permutation(-np.log1p(-_quantiles(n)))
                gaps *= length / gaps.sum()
                due = start + (np.cumsum(gaps) - gaps[0])
                reqs = self._make_block(n)
                for r, t in zip(reqs, due):
                    r.due_s = float(t)
                out.extend(reqs)
            start += length
        return out

    # -- closed loop -----------------------------------------------------
    def first_requests(self) -> List[Req]:
        """One request per client; with `stagger`, client c's asks for a
        stratified share of its drawn output length."""
        c = int(self.mix["clients"])
        reqs = [self.next_request() for _ in range(c)]
        if self.mix.get("stagger"):
            share = self.rng.permutation(_quantiles(c))
            for r, u in zip(reqs, share):
                r.max_new = max(1, int(math.ceil(u * r.max_new)))
        return reqs

    def next_request(self) -> Req:
        """The next request of a closed loop, from stratified blocks of
        4 x clients requests."""
        if not self._block:
            self._block = self._make_block(4 * int(self.mix["clients"]))
        return self._block.pop(0)

    def max_len(self) -> int:
        """Longest prompt plus output any request of this mix can have."""
        pre = self.mix.get("prefixes")
        p = int(self.mix["prompt"]["max"]) + (int(pre["len"]) if pre else 0)
        return p + int(self.mix["output"]["max"])
