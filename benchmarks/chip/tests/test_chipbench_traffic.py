"""The traffic generator: repeatable by seed, the same work for every
seed, and the mixes' own arithmetic."""
import collections

import numpy as np
import pytest

import _paths  # noqa: F401
from chipbench import traffic, warmup

CHAT = {"loop": "open", "rate_rps": 4.0, "lead_s": 2,
        "prompt": {"dist": "pareto", "alpha": 1.2, "min": 64, "max": 2048},
        "output": {"dist": "uniform", "min": 32, "max": 256},
        "sampled_share": 0.5, "sampling": {"temperature": 0.8, "top_k": 40}}
PREFIXED = dict(CHAT, prefixes={"count": 8, "len": 40, "zipf_s": 1.1},
                prompt={"dist": "pareto", "alpha": 1.2, "min": 16,
                        "max": 256})
CLOSED = {"loop": "closed", "clients": 6, "stagger": True,
          "prompt": {"dist": "uniform", "min": 100, "max": 300},
          "output": {"dist": "uniform", "min": 20, "max": 60},
          "sampled_share": 0.5, "sampling": {"temperature": 0.8, "top_k": 40}}
PHASES = [2.0, 10.0, 5.0]
BIG_SEED = 2**31 + 12345


def _sig(reqs):
    return [(r.uid, r.due_s, r.max_new, r.sampled, r.sample_seed, r.prefix,
             r.prompt.tobytes()) for r in reqs]


@pytest.mark.parametrize("mix", [CHAT, PREFIXED], ids=["chat", "prefixed"])
def test_open_loop_repeats_by_seed(mix):
    a = traffic.Traffic(mix, 1000, BIG_SEED).open_requests(PHASES)
    b = traffic.Traffic(mix, 1000, BIG_SEED).open_requests(PHASES)
    c = traffic.Traffic(mix, 1000, BIG_SEED + 1).open_requests(PHASES)
    assert _sig(a) == _sig(b)
    assert _sig(a) != _sig(c)


@pytest.mark.parametrize("mix", [CHAT, PREFIXED], ids=["chat", "prefixed"])
def test_every_seed_gets_the_same_work_per_phase(mix):
    """Each phase's requests have the same sizes, classes and prefix
    picks whatever the seed; only order and content change."""
    def per_phase(seed):
        reqs = traffic.Traffic(mix, 1000, seed).open_requests(PHASES)
        edges = np.cumsum([0.0] + PHASES)
        out = []
        for lo, hi in zip(edges, edges[1:]):
            ph = [r for r in reqs if lo <= r.due_s < hi]
            out.append(sorted((len(r.prompt), r.max_new, r.sampled, r.prefix
                               if r.prefix is not None else -1) for r in ph))
        return out
    assert per_phase(1) == per_phase(2) == per_phase(BIG_SEED)
    counts = [len(p) for p in per_phase(3)]
    assert counts == [traffic.phase_count(mix["rate_rps"], s)
                      for s in PHASES]


def test_open_loop_due_times_span_their_phase():
    reqs = traffic.Traffic(CHAT, 1000, 7).open_requests(PHASES)
    due = np.array([r.due_s for r in reqs])
    assert np.all(due >= 0) and np.all(due < sum(PHASES))
    window = due[(due >= 2.0) & (due < 12.0)]
    assert len(window) == 40 and window.min() == pytest.approx(2.0)


def test_lengths_follow_the_mix():
    p = traffic.lengths(CHAT["prompt"], 1000)
    assert p.min() == 64 and p.max() == 2048
    assert np.median(p) < 150          # Pareto 1.2: most prompts are short
    u = traffic.lengths(CLOSED["output"], 41)
    assert u.min() == 20 and u.max() == 60 and len(set(u)) == 41


def test_zipf_counts_sum_and_rank():
    c = traffic.zipf_counts(100, 8, 1.1)
    assert c.sum() == 100 and list(c) == sorted(c, reverse=True)


def test_prefixed_requests_open_with_their_prefix():
    t = traffic.Traffic(PREFIXED, 1000, 5)
    for r in t.open_requests(PHASES):
        assert np.array_equal(r.prompt[:40], t.prefixes[r.prefix])
        assert 16 <= len(r.prompt) - 40 <= 256


def test_closed_loop_repeats_and_staggers():
    a = traffic.Traffic(CLOSED, 1000, BIG_SEED)
    b = traffic.Traffic(CLOSED, 1000, BIG_SEED)
    fa, fb = a.first_requests(), b.first_requests()
    assert _sig(fa) == _sig(fb)
    assert len(fa) == 6
    nxt = [a.next_request() for _ in range(30)]
    assert _sig(nxt) == _sig([b.next_request() for _ in range(30)])
    # a block of 4 x clients requests holds the stratified sizes
    block = collections.Counter(len(r.prompt) for r in fa + nxt[:18])
    assert sum(block.values()) == 24


def test_warmup_sizes_cover_the_traffic():
    prompts, suffixes = warmup.size_sets(PREFIXED, PHASES)
    reqs = traffic.Traffic(PREFIXED, 1000, 9).open_requests(PHASES)
    assert {len(r.prompt) for r in reqs} <= set(prompts)
    assert {len(r.prompt) - 40 for r in reqs} <= set(suffixes)
    prompts, suffixes = warmup.size_sets(CHAT, PHASES)
    reqs = traffic.Traffic(CHAT, 1000, 9).open_requests(PHASES)
    assert {len(r.prompt) for r in reqs} <= set(prompts) and not suffixes
