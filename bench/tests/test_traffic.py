"""Both generator kinds at a tiny size: seeded, repeatable, and shaped as
their mix files say."""
import numpy as np
import pytest

from bench import spec


def mix(name):
    return spec.load_json(f"{spec.BENCH}/traffic/{name}.json")


def drain(gen, until_us, step_us=5e4):
    out, t = [], 0.0
    while t <= until_us:
        out.append(gen.due(t))
        t += step_us
    return [np.concatenate(x) for x in zip(*out)]


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 11, 2 ** 33])
def test_open_poisson_is_seeded_and_flat(seed):
    m = mix("live")
    gen = spec.kind("traffic", m["generator"])
    p = dict(m["params"], rate_per_s=4000.0)
    a = gen.make(p, 64, 2.0, seed)
    b = gen.make(p, 64, 2.0, seed)
    da, va, sa = drain(a, 3e6)
    db, vb, sb = drain(b, 3e6)
    assert np.array_equal(da, db) and np.array_equal(va, vb) \
        and np.array_equal(sa, sb)
    assert not a.closed and a.tail_s == m["params"]["tail_s"]
    horizon = (2.0 + a.tail_s)
    # the fleet's total is the stated rate (bursts add a little)
    assert 0.97 < len(sa) / horizon / 4000.0 < 1.15
    assert np.all(np.diff(sa) >= 0) and sa[-1] < horizon * 1e6
    for d in range(64):                     # strictly increasing per device
        assert np.all(np.diff(sa[da == d]) > 0)
    assert va.min() >= p["value_lo"] and va.max() <= p["value_hi"]
    other = gen.make(p, 64, 2.0, seed + 1)
    assert not np.array_equal(drain(other, 3e6)[2][:100], sa[:100])


def test_open_poisson_due_is_by_schedule():
    m = mix("live")
    gen = spec.kind("traffic", m["generator"]).make(
        dict(m["params"], rate_per_s=1000.0), 8, 1.0, 5)
    d, v, s = gen.due(2e5)
    assert len(s) and s.max() <= 2e5
    d2, v2, s2 = gen.due(1e5)                 # nothing is posted twice
    assert len(s2) == 0
    d3, v3, s3 = gen.due(4e5)
    assert s3.min() > 2e5 and s3.max() <= 4e5
    assert np.array_equal(gen.ts(s3, 0), s3)


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 3])
def test_wave_is_seeded_per_step(seed):
    m = mix("wave")
    gen = spec.kind("traffic", m["generator"])
    a, b = gen.make(m["params"], 256, 1.0, seed), \
        gen.make(m["params"], 256, 1.0, seed)
    assert a.closed and a.tail_s == 0.0
    counts = []
    for k in range(1, 25):
        da, va, sa = a.due(k * 1000.0)
        db, vb, sb = b.due(k * 1000.0)
        assert np.array_equal(da, db) and np.array_equal(va, vb)
        assert np.all(sa == k * 1000) and np.all(a.ts(sa, k) == k)
        assert len(np.unique(da)) == len(da)
        counts.append(len(da))
    # one day of steps: the mean rate is about base_rate (bursts add some)
    assert 0.2 < np.mean(counts) / 256 < 0.35
