"""Each mix gives the same schedule for the same seed and another for another."""

import pathlib

import numpy as np
import pytest

from bench import traffic
from bench.tests.tiny import OPEN_MIX

MIXES = sorted(p.stem for p in (pathlib.Path(traffic.__file__).parent / "traffic").glob("*.json"))
BIG_SEED = 2**31 + 12345


def _key(schedule):
    return [(round(a.due_s, 9), tuple(a.prompt), a.max_new_tokens) for a in schedule]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_schedule(mix):
    m = traffic.load_mix(mix)
    a = traffic.schedule(m, BIG_SEED, 32000, 40)
    b = traffic.schedule(m, BIG_SEED, 32000, 40)
    assert a and _key(a) == _key(b)


@pytest.mark.parametrize("mix", MIXES)
def test_other_seed_other_schedule(mix):
    m = traffic.load_mix(mix)
    a = traffic.schedule(m, BIG_SEED, 32000, 40)
    b = traffic.schedule(m, BIG_SEED + 1, 32000, 40)
    assert _key(a) != _key(b)


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_respect_the_mix(mix):
    m = traffic.load_mix(mix)
    for a in traffic.schedule(m, 7, 32000, 40):
        assert m["prompt_tokens"]["min"] <= len(a.prompt) <= m["prompt_tokens"]["max"]
        assert m["output_tokens"]["min"] <= a.max_new_tokens <= m["output_tokens"]["max"]
        assert all(0 <= t < 32000 for t in a.prompt)


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_lengths(mix):
    """Seeds reorder one set of lengths and gaps in each part of the schedule:
    the window offers the same work to every seed."""
    m = traffic.load_mix(mix)

    def window(seed):
        w = [a for a in traffic.schedule(m, seed, 32000, 40) if 0 <= a.due_s < 40]
        gaps = np.diff([a.due_s for a in w] + [40.0])
        return (sorted(len(a.prompt) for a in w), sorted(a.max_new_tokens for a in w),
                sorted(np.round(gaps, 9)), [len(a.prompt) for a in w])

    a, b = window(1), window(2**33 + 5)
    assert a[:3] == b[:3] and a[3] != b[3]
    grid = traffic.length_grid(m["prompt_tokens"], 1001)
    assert abs(int(np.median(grid)) - m["prompt_tokens"]["median"]) <= 1


def test_open_loop_gaps_have_the_rate():
    gaps = traffic.gap_grid(2.0, 1000, 500.0)
    assert abs(gaps.sum() - 500.0) < 1e-9
    # exponential: the median gap is ln 2 / rate
    assert abs(np.median(gaps) - np.log(2) / 2.0) < 0.01


@pytest.mark.parametrize("mix", MIXES)
def test_window_holds_a_fixed_number_of_arrivals(mix):
    m = traffic.load_mix(mix)
    counts = {sum(0 <= a.due_s < 51 for a in traffic.schedule(m, s, 32000, 51))
              for s in (1, 2, 3, 2**33)}
    assert counts == {round(m["rate_per_s"] * 51)}


@pytest.mark.parametrize("mix", MIXES)
def test_schedule_has_its_three_parts(mix):
    """The preroll, the window and the drain each hold round(rate x length)
    arrivals, in the order they are due."""
    m = traffic.load_mix(mix)
    due = [a.due_s for a in traffic.schedule(m, BIG_SEED, 32000, 51)]
    assert due == sorted(due)
    for lo, hi in ((-m["preroll_s"], 0), (0, 51), (51, 51 + m["drain_s"])):
        assert sum(lo <= t < hi for t in due) == round(m["rate_per_s"] * (hi - lo))


def test_bursts_arrive_together():
    """A burst of k shares its due time; the part keeps round(rate x length)
    arrivals, the last burst holding what is left, whatever the seed: the
    tiny mix's 3 s window holds a burst of 8 and one of 4."""
    def window(seed):
        due = [a.due_s for a in traffic.schedule(OPEN_MIX, seed, 512, 3.0)
               if 0 <= a.due_s < 3.0]
        return sorted(np.unique(due, return_counts=True)[1].tolist()), due[0]

    assert window(BIG_SEED) == window(BIG_SEED + 1) == ([4, 8], 0.0)

