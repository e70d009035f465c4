"""The session generator: determinism per seed, grid, width bounds,
arrivals, and a warm-up that meets every shape the stream asks for."""
import numpy as np
import pytest

from bench import harness

PARAMS = {"tenants": 64, "tenant_zipf": 1.1,
          "ops": {"drill": 0.35, "pan": 0.35, "zoom_out": 0.15, "jump": 0.15},
          "width_parts": [1, 64], "grid_docs": 32}
B, DOMAIN = 256, 155 * 256


@pytest.fixture(scope="module")
def traffic():
    return harness.load_module("traffic", "sessions")


def take(traffic, seed, n=2000, params=PARAMS):
    s = traffic.stream(params, DOMAIN, B, seed, 0, n=n)
    return [s.next() for _ in range(n)]


def test_same_seed_same_queries(traffic):
    assert take(traffic, 2**33 + 5, 300) == take(traffic, 2**33 + 5, 300)
    assert take(traffic, 1, 300) != take(traffic, 2, 300)
    assert traffic.arrivals(PARAMS, 8.0, 30.0, 7) == \
        traffic.arrivals(PARAMS, 8.0, 30.0, 7)


def test_grid_and_widths(traffic):
    qs = take(traffic, 3)
    for q in qs:
        assert q.lo % 32 == 0 and q.hi % 32 == 0
        assert 0 <= q.lo < q.hi <= DOMAIN
        assert B <= q.hi - q.lo <= 64 * B
    widths = {q.hi - q.lo for q in qs}
    assert min(widths) == B and max(widths) == 64 * B
    assert len({q.tenant for q in qs}) > 32


def test_covered_grid_lands_on_partitions(traffic):
    p = dict(PARAMS, grid_docs=B)
    for q in take(traffic, 4, 500, p):
        assert q.lo % B == 0 and q.hi % B == 0


def test_arrivals_are_poisson(traffic):
    a = np.array(traffic.arrivals(PARAMS, 10.0, 200.0, 9))
    assert len(a) == pytest.approx(2000, rel=0.1)
    assert np.all(np.diff(a) > 0) and a[-1] < 200.0


def test_warmup_meets_every_shape(traffic):
    def shapes(ranges):
        out = set()
        for lo, hi in ranges:
            (s0, s1), gaps = harness.expected_plan(lo, hi, B)
            out.add(("merge", (s1 - s0) // B + len(gaps)))
            out.update(("gap", c - a) for a, c in gaps)
        return out

    warm = shapes(traffic.warmup(PARAMS, DOMAIN, B))
    seen = shapes((q.lo, q.hi) for seed in range(3)
                  for q in take(traffic, seed, 3000))
    assert seen <= warm


def test_pool_gives_every_seed_the_same_work(traffic):
    a, b = take(traffic, 1, 400), take(traffic, 2**33 + 2, 400)
    assert a != b
    assert sorted(a, key=repr) == sorted(b, key=repr)
    assert take(traffic, 1, 400) == a
    # each analyst's queries keep their session order
    for t in {q.tenant for q in a}:
        assert [q for q in a if q.tenant == t] == \
            [q for q in b if q.tenant == t]
    x = traffic.arrivals(PARAMS, 5.0, 60.0, 1)
    y = traffic.arrivals(PARAMS, 5.0, 60.0, 2)
    assert x != y and abs(len(x) - len(y)) <= 1
    # the same gaps: each of one run's gaps is one of the other's
    gx, gy = np.diff([0.0] + x), set(np.round(np.diff([0.0] + y), 12))
    assert sum(np.round(g, 12) in gy for g in gx) >= len(gx) - 5
