"""Percentile and open-loop arithmetic."""

import math
import types

import numpy as np
import pytest

from harness import serve_loop, stats, traffic


def test_nearest_rank_percentile():
    v = list(range(1, 11))
    assert stats.percentile(v, 90) == 9
    assert stats.percentile(v, 95) == 10
    assert stats.percentile(v, 50) == 5
    assert stats.percentile([3.0], 99) == 3.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_a_request_with_no_answer_ranks_at_inf():
    v = [0.1] * 9 + [math.inf]
    assert stats.percentile(v, 90) == 0.1
    assert stats.percentile(v, 91) == math.inf
    assert stats.beyond(v, 90) == 1


def test_window_metrics_time_from_due_and_count_the_missing():
    arr = [traffic.Arrival(rid=i, due=due, prompt=[1, 2], max_new_tokens=3,
                           in_window=w)
           for i, (due, w) in enumerate([(0.5, False), (10.0, True),
                                         (11.0, True), (19.5, True)])]
    loop = types.SimpleNamespace(
        sched=arr,
        stamps={0: [5.0, 10.5, 12.0], 1: [12.0, 12.5, 13.0],
                2: [14.0, 21.0], 3: []},
        submitted={0: 0.5, 1: 10.001, 2: 11.002, 3: 19.5})
    wm = serve_loop.window_metrics(loop, origin=0.0, w0=10.0, w1=20.0,
                                     in_window=lambda a: a.in_window)
    assert wm["rids"] == [1, 2, 3]
    # request 2's first token is due-relative; request 3 has none
    assert wm["ttft"] == [pytest.approx(2.0), pytest.approx(3.0), math.inf]
    # tokens stamped inside the window: 10.5, 12.0 | 12.0, 12.5, 13.0 | 14.0
    assert wm["out_tokens"] == 6
    # gaps whose later token lies in the window (21.0 does not)
    assert sorted(wm["gaps"]) == pytest.approx([0.5, 0.5, 1.5, 5.5])
    assert wm["late"] == pytest.approx([0.0, 0.001, 0.002, 0.0])


def test_schedule_is_stratified_and_seed_shuffled():
    mix = {"arrival": "poisson", "rate_rps": 2.0, "warmup_s": 5,
           "prompt": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                      "min": 32, "max": 1024},
           "output": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                      "min": 8, "max": 512}}
    a = traffic.serve_schedule(mix, 1, 10, vocab=1000)
    b = traffic.serve_schedule(mix, 2**33 + 1, 10, vocab=1000)
    assert len(a) == len(b) == 10 + 20
    # the same sizes in another order
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt)
                                                      for x in b)
    assert sorted(x.max_new_tokens for x in a) == sorted(
        x.max_new_tokens for x in b)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    win = [x.due for x in a if x.in_window]
    assert 5 < min(win) and max(win) < 15
    assert np.all(np.diff([x.due for x in a]) > 0)
