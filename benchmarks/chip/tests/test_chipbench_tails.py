"""The end-to-end arithmetic: tails over every request, unfinished ones
at their elapsed time, and tokens per second over the window."""
import pytest

import _paths  # noqa: F401
from chipbench import report, tails
from chipbench.harness import Outcome, Record


def rec(due, times, done=True):
    r = Record(req=None, due=due)
    r.times = list(times)
    r.done = done
    return r


def test_percentile_nearest_rank():
    s = list(range(1, 101))
    assert tails.percentile(s, 95) == 95
    assert tails.percentile(s, 50) == 50
    assert tails.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        tails.percentile([], 95)


def test_ttft_counts_unfinished_requests_at_elapsed_time():
    records = [rec(10.0, [10.5, 10.6]), rec(11.0, [], done=False),
               rec(9.0, [9.1]),                 # due before the window
               rec(20.0, [])]                   # due after it
    got = tails.ttft_samples(records, 10.0, 20.0, t_end=25.0)
    assert got == pytest.approx([0.5, 14.0])


def test_itl_gaps_ending_in_the_window():
    r = rec(0.0, [9.0, 9.5, 10.5, 19.0, 21.0])
    assert tails.itl_samples([r], 10.0, 20.0) == pytest.approx([1.0, 8.5])


def test_output_tok_s_over_the_window():
    records = [rec(0.0, [9.9, 10.0, 10.5, 11.99, 12.0]),
               rec(0.0, [11.0])]
    assert tails.tokens_in(records, 10.0, 12.0) == 4
    assert tails.output_tok_s(records, 10.0, 12.0) == pytest.approx(2.0)


def test_attempted_and_failed():
    out = Outcome(records=[rec(10.0, [10.2]), rec(11.0, [], done=False),
                           rec(5.0, [])], w0=10.0, w1=20.0, t_end=30.0,
                  stats=None, steps=[])
    assert report.counts(out, open_loop=True) == (2, 1)
    assert report.counts(out, open_loop=False) == (2, 0)
    assert report.end_to_end("setup_s", out, 1.5) == 1.5
    assert report.end_to_end("output_tok_s", out, 1.0) == pytest.approx(
        0.1)


def test_suffixed_metric_reads_its_quantity():
    """`itl_p95_ms.prefix` is `itl_p95_ms`, held under its own bound."""
    out = Outcome(records=[rec(10.0, [10.2, 10.3, 10.45, 10.9])], w0=10.0,
                  w1=20.0, t_end=30.0, stats=None, steps=[])
    want = report.end_to_end("itl_p95_ms", out, 0.0)
    assert want == pytest.approx(450.0)
    assert report.end_to_end("itl_p95_ms.prefix", out, 0.0) == want
    with pytest.raises(KeyError):
        report.end_to_end("ttft_p95_ms", out, 0.0)
