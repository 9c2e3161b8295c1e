"""The end-to-end metrics' arithmetic on synthetic timelines."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
sys.path.insert(0, REPO)

from perfbench.harness import metrics  # noqa: E402
from perfbench.harness import readers  # noqa: E402


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 3.0), (100, 5.0),
                                    (95, 4.8), (25, 2.0)])
def test_percentile_interpolates(q, want):
    assert metrics.percentile([5, 1, 4, 2, 3], q) == pytest.approx(want)


def test_normalised_latency_is_timed_from_when_the_request_was_due():
    run = {
        "outcomes": [
            {"due": 0.0, "sent": 0.5, "done": 10.0, "ok": True,
             "output_tokens": 100},
            {"due": 1.0, "sent": 1.0, "done": 3.0, "ok": True,
             "output_tokens": 10},
            {"due": 2.0, "sent": 2.0, "done": 4.0, "ok": False,
             "output_tokens": 10},
            {"due": 9.0, "sent": 9.0, "done": 9.9, "ok": True,
             "output_tokens": 3},
        ],
        "judged": [0, 1, 2],
    }
    assert readers.normalised_latency(run, 0) == pytest.approx(0.1)
    assert readers.normalised_latency(run, 100) == pytest.approx(0.2)
    assert readers.normalised_latency(run, 50) == pytest.approx(0.15)


def test_slope():
    assert metrics.slope([(0, 1), (1, 3), (2, 5)]) == pytest.approx(2.0)
    assert metrics.slope([(0, 4), (5, 4)]) == pytest.approx(0.0)
    assert metrics.slope([(1, 1)]) is None
