"""The load generator: deterministic in the seed, the same work for every
seed, Poisson arrivals at the stated rate, latency from the due time."""
import collections
import contextlib
import json
import pathlib

import numpy as np
import pytest

from bench import data, load, reference

TRAFFIC = pathlib.Path(__file__).resolve().parents[1] / "traffic"
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json"))
BIG_SEED = 2**31 + 977


def mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def _key(reqs):
    return [(r.due_s, r.design, r.target, r.grid, r.tenant) for r in reqs]


@pytest.mark.parametrize("name", MIXES)
def test_deterministic_in_the_seed(name):
    m = mix(name)
    a = load.requests(m, BIG_SEED, 400, 30.0)
    b = load.requests(m, BIG_SEED, 400, 30.0)
    c = load.requests(m, BIG_SEED + 1, 400, 30.0)
    assert _key(a) == _key(b)
    if m["designs"] > 1 or m.get("targets", 1) > 1:
        assert _key(a) != _key(c)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work(name):
    m = mix(name)
    a = load.requests(m, 3, 400, 30.0)
    b = load.requests(m, 2**33 + 5, 400, 30.0)
    assert sorted(collections.Counter(r.design for r in a).values()) == \
        sorted(collections.Counter(r.design for r in b).values())
    assert collections.Counter(r.grid for r in a) == \
        collections.Counter(r.grid for r in b)
    gaps = [np.sort(np.diff([0.0] + [r.due_s for r in x])) for x in (a, b)]
    np.testing.assert_allclose(gaps[0], gaps[1], rtol=1e-9, atol=1e-12)


def test_poisson_due_times_average_the_rate():
    m = dict(mix("zipf_open"), rate_per_s=7.5)
    n = load.offered(m, 40.0)
    assert n == 300
    due = np.array([r.due_s for r in load.requests(m, 11, n, 40.0)])
    assert np.all(np.diff(due) >= 0) and 0 < due[0] and due[-1] < 40.0
    assert n / 40.0 == pytest.approx(7.5)
    gaps = np.diff(np.concatenate([[0.0], due]))
    assert gaps.mean() == pytest.approx(1 / 7.5, rel=0.02)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.1)


def test_zipf_popularity_and_the_shifted_grid():
    m = mix("zipf_open")
    reqs = load.requests(m, 5, 400, 30.0)
    counts = sorted(collections.Counter(r.design for r in reqs).values(),
                    reverse=True)
    w = 1 / np.arange(1, 17) ** 1.2
    assert counts[0] == pytest.approx(400 * w[0] / w.sum(), abs=1)
    shifted = len(m["grids"])
    assert [r.grid == shifted for r in reqs[:16]] == \
        [i % 8 == 7 for i in range(16)]
    assert len({r.tenant for r in reqs}) == 6


@pytest.mark.parametrize("h", [128, 256])
def test_lambda_star_lies_inside_the_grid(h):
    """The designs' hold-out optimum is interior to the paper's grid, so a
    wrong pick is not hidden at an end."""
    x, y = data.make_design(data.seed_key(BIG_SEED, 1, 0), 4 * h, h, 1)
    lams = data.log_grid(1e-3, 1.0, 31)
    (curve,) = reference.cv_curves(x, y.T, k=5, grids=[lams], g=4,
                                   degree=2, block=64)
    i = int(np.argmin(curve[:, 0]))
    assert 0 < i < 30


def test_designs_are_deterministic_for_large_seeds():
    a = data.make_design(data.seed_key(2**31 + 3, 1, 0), 64, 16, 2)
    b = data.make_design(data.seed_key(2**31 + 3, 1, 0), 64, 16, 2)
    c = data.make_design(data.seed_key(3, 1, 0), 64, 16, 2)
    assert all(np.array_equal(u, v) for u, v in zip(a, b))
    assert not np.array_equal(a[0], c[0])


class _Server:
    """Answers each request after a fixed service time, one at a time."""

    def __init__(self, service_s):
        self.service_s, self.queue, self.next_id = service_s, [], 0

    @property
    def pending(self):
        return len(self.queue)

    def submit(self, req):
        self.queue.append(self.next_id)
        self.next_id += 1
        return self.next_id - 1

    def step(self):
        import time

        time.sleep(self.service_s)
        rid = self.queue.pop(0)
        return [type("R", (), dict(request_id=rid, result=object(),
                                   batch_size=1))()]


def test_latency_is_measured_from_the_due_time():
    """Three requests due together behind a 0.1 s server: the third waits
    for the first two, and its latency counts that wait."""
    reqs = [load.Request(i, 0.05, 0, 0, 0, "t") for i in range(3)]
    records, window_s = load.open_loop(_Server(0.1), lambda r: r, reqs, 0.5,
                                       lambda name: contextlib.nullcontext())
    lat = sorted(r.done_s - r.request.due_s for r in records)
    assert window_s == 0.5
    for i, got in enumerate(lat):
        assert got == pytest.approx(0.1 * (i + 1), abs=0.03)
    assert all(r.submit_s >= r.request.due_s for r in records)
