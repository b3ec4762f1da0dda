"""The traffic generator: the same work for every seed, in another order,
and arrivals stamped at the times they were due."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import loadgen  # noqa: E402

MIXES = sorted(p.stem for p in (ROOT / "bench" / "traffic").glob("*.json"))


def load(name):
    """A mix as a run reads it; an open-loop mix written without a rate
    (its knee not yet found on the chip) gets one for the test."""
    mix = loadgen.load_mix(name)
    if mix["arrival"] == "poisson":
        mix.setdefault("rate_rps", 3.0)
    return mix


@pytest.mark.parametrize("name", MIXES)
def test_mix_same_work_every_seed(name):
    mix = load(name)
    a = loadgen.plan(mix, 10.0, 11, 49152)
    b = loadgen.plan(mix, 10.0, 2**31 + 12345, 49152)
    assert len(a) == len(b) == loadgen.request_count(mix, 10.0)
    for key in (lambda p: len(p.prompt), lambda p: p.max_new_tokens):
        assert sorted(map(key, a)) == sorted(map(key, b))
    # the interarrival gaps, and the one from the last arrival to the close
    gaps = [np.diff([p.due_s for p in x] + [10.0]).round(9).tolist()
            for x in (a, b)]
    assert sorted(gaps[0]) == sorted(gaps[1])
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    assert all(lo <= len(p.prompt) <= hi for p in a)


@pytest.mark.parametrize("name", MIXES)
def test_mix_deterministic_by_seed(name):
    mix = load(name)
    a = loadgen.plan(mix, 5.0, 7, 512)
    b = loadgen.plan(mix, 5.0, 7, 512)
    assert a == b
    assert all(0 <= t < 512 for p in a for t in p.prompt)


def test_open_loop_dues_fill_the_window():
    mix = {"arrival": "poisson", "rate_rps": 3.0,
           "prompt_tokens": {"dist": "uniform", "min": 4, "max": 9},
           "output_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                             "min": 2, "max": 20}}
    plan = loadgen.plan(mix, 20.0, 5, 100)
    due = [p.due_s for p in plan]
    assert len(plan) == 60
    assert due[0] == 0.0 and due == sorted(due) and due[-1] < 20.0
    assert np.mean(np.diff(due)) == pytest.approx(20.0 / 60, rel=0.05)


def test_offline_head_fixed_across_seeds():
    mix = loadgen.load_mix("offline")
    head = mix["fixed_head"]
    a = loadgen.plan(mix, 10.0, 1, 49152)
    b = loadgen.plan(mix, 10.0, 2, 49152)
    for key in (lambda p: len(p.prompt), lambda p: p.max_new_tokens):
        assert [key(p) for p in a[:head]] == [key(p) for p in b[:head]]
        assert [key(p) for p in a[head:]] != [key(p) for p in b[head:]]
    assert all(p.due_s == 0.0 for p in a)


def test_poisson_mix_without_rate_refused():
    mix = {"arrival": "poisson",
           "prompt_tokens": {"dist": "uniform", "min": 4, "max": 9},
           "output_tokens": {"dist": "uniform", "min": 2, "max": 5}}
    with pytest.raises(ValueError, match="rate_rps"):
        loadgen.plan(mix, 10.0, 1, 100)


def test_quantile_lengths_hand_values():
    u = {"dist": "uniform", "min": 10, "max": 13}
    assert loadgen._quantiles(u, 4).tolist() == [10, 11, 12, 13]
    ln = {"dist": "lognormal", "median": 100, "sigma": 1.0, "min": 1,
          "max": 10**6}
    assert loadgen._quantiles(ln, 1).tolist() == [100]


def test_prompt_buckets():
    assert loadgen.prompt_buckets([5, 16, 17, 100, 1536]) == {
        16: 16, 32: 17, 128: 100, 2048: 1536}
