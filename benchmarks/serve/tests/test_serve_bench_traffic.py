"""The load generators: seeded, clipped, closed and open loops."""

import json
import os

import numpy as np
import pytest

from serve_bench_util import BENCH, DATA

import traffic as T


def mix_file(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def tiny_mix():
    with open(os.path.join(DATA, "tiny_traffic.json")) as f:
        return json.load(f)


MIXES = {"decode": lambda: mix_file("decode"), "tiny": tiny_mix}


@pytest.mark.parametrize("name", sorted(MIXES))
def test_same_seed_same_requests(name):
    spec = MIXES[name]()
    a, b = T.Mix(spec, 2**33 + 5, 151936), T.Mix(spec, 2**33 + 5, 151936)
    for i in (0, 1, 63, 64, 200):
        assert a.sizes(i) == b.sizes(i)
        np.testing.assert_array_equal(a.prompt(i), b.prompt(i))
    # Another seed: the same sizes, other prompt tokens.
    c = T.Mix(spec, 2**33 + 6, 151936)
    assert [a.sizes(i) for i in range(200)] == [c.sizes(i) for i in range(200)]
    assert not np.array_equal(a.prompt(5), c.prompt(5))


@pytest.mark.parametrize("name", sorted(MIXES))
def test_lengths_within_clips_and_every_seed_the_same_set(name):
    spec = MIXES[name]()
    mixes = [T.Mix(spec, s, 1000) for s in (1, 2, 3**30)]
    for mix in mixes:
        sizes = [mix.sizes(i) for i in range(3 * mix.block)]
        for p, o in sizes:
            assert spec["prompt"]["min"] <= p <= spec["prompt"]["max"]
            assert spec["output"]["min"] <= o <= spec["output"]["max"]
        assert len(mix.prompt(5)) == mix.sizes(5)[0]
        assert mix.prompt(5).min() >= 1 and mix.prompt(5).max() < 1000
    blocks = [sorted(m.sizes(i)[0] for i in range(m.block)) for m in mixes]
    assert blocks[0] == blocks[1] == blocks[2]
    assert blocks[0] == sorted(mixes[0].prompt_q)


def test_lognormal_quantiles_follow_the_median():
    q = T.quantiles({"dist": "lognormal", "median": 256, "sigma": 0.8, "min": 1, "max": 10**6}, 64)
    assert abs(np.median(q) - 256) <= 8
    assert list(q) == sorted(q)


def test_closed_loop_sends_only_after_completion():
    spec = mix_file("decode")
    gen = T.ClosedLoop(T.Mix(spec, 7, 1000), clients=3)
    gen.start(0.0)
    first = gen.due(0.0)
    assert [r.client for r in first] == [0, 1, 2]
    assert gen.due(100.0) == []  # nobody has completed: nothing more is sent
    gen.complete(first[1], 5.0)
    nxt = gen.due(5.0)
    assert [(r.client, r.due) for r in nxt] == [(1, 5.0)]
    assert gen.due(1e9) == []


def test_closed_loop_stagger_spreads_first_outputs():
    spec = mix_file("decode")
    mix = T.Mix(spec, 7, 1000)
    gen = T.ClosedLoop(mix, clients=4)
    gen.start(0.0)
    first = gen.due(0.0)
    for c, r in enumerate(first):
        lane = int(gen.lane[c])
        assert r.out_len == max(1, -(-mix.sizes(lane)[1] * (2 * lane + 1) // 8))


def test_closed_loop_every_seed_the_same_lanes_on_other_clients():
    """Lane l runs layout entries l, l + clients, ...; the seed only draws
    which client runs it, so every seed asks for the same shapes."""
    spec = mix_file("decode")
    runs = {}
    for seed in (3, 2**33 + 1):
        gen = T.ClosedLoop(T.Mix(spec, seed, 1000), clients=4)
        gen.start(0.0)
        by_lane = {}
        for step in range(3):
            for r in gen.due(float(step)):
                lane = int(gen.lane[r.client])
                by_lane.setdefault(lane, []).append((r.prompt_len, r.out_len))
                gen.complete(r, float(step + 1))
        runs[seed] = (by_lane, list(gen.lane))
    (a, lanes_a), (b, lanes_b) = runs.values()
    assert a == b and sorted(a) == [0, 1, 2, 3]
    assert lanes_a != lanes_b
    mix = T.Mix(spec, 0, 1000)
    assert [o for _, o in a[2][1:]] == [mix.sizes(2 + 4 * k)[1] for k in (1, 2)]


def test_open_loop_poisson_times_requests_from_their_due_time():
    spec = mix_file("decode")
    gen = T.OpenLoop(T.Mix(spec, 9, 1000), {"kind": "poisson", "rate": 4.0})
    sched = gen.schedule(400)
    assert all(b > a for a, b in zip(sched, sched[1:]))
    assert len(sched) / sched[-1] == pytest.approx(4.0, rel=0.1)
    gen.start(10.0)
    late = gen.due(10.0 + 2.0)  # the generator ran 2 s after its start
    assert [r.due for r in late] == pytest.approx([10.0 + t for t in sched if t <= 2.0])
    assert all(r.due <= 12.0 for r in late)
    # How late each was sent is the harness's submit time less this due time.
    assert max(12.0 - r.due for r in late) > 0


def test_open_loop_on_off_bursts():
    spec = mix_file("decode")
    arr = {"kind": "onoff", "rate": 2.0, "burst_factor": 4, "on_s": 2, "period_s": 10}
    sched = np.array(T.OpenLoop(T.Mix(spec, 11, 1000), arr).schedule(2000))
    assert len(sched) / sched[-1] == pytest.approx(2.0, rel=0.05)
    phase = sched % 10
    on_rate = np.sum(phase < 2) / (2 * sched[-1] / 10)
    off_rate = np.sum(phase >= 2) / (8 * sched[-1] / 10)
    assert on_rate == pytest.approx(8.0, rel=0.1)
    assert off_rate == pytest.approx(0.5, rel=0.15)
    with pytest.raises(ValueError):
        T.OpenLoop(T.Mix(spec, 11, 1000), {**arr, "burst_factor": 6})
