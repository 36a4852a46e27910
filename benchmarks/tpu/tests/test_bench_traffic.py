"""Seeded traffic: determinism, clipping, the same work for every seed,
and open-loop due times fixed before any service."""
from __future__ import annotations

import contextlib
import time

import numpy as np
import pytest

import tinycells  # noqa: F401  (puts the benchmark on the path)
import traffic

CHAT = {"rate_per_s": 6.5,
        "prompt": {"median": 512, "sigma": 0.9, "min": 32, "max": 2048},
        "output": {"median": 128, "sigma": 0.9, "min": 8, "max": 512}}
LONG = {"rate_per_s": 2.0,
        "prompt": {"median": 2048, "sigma": 0.5, "min": 512, "max": 4000},
        "output": {"median": 48, "sigma": 0.5, "min": 16, "max": 128}}


def _key(reqs):
    return [(r.due_s, r.prompt.tobytes(), r.max_new_tokens) for r in reqs]


@pytest.mark.parametrize("mix", [CHAT, LONG], ids=["chat", "long"])
def test_same_seed_same_requests(mix):
    a = traffic.requests(mix, 151552, 2 ** 31 + 12345, 30.0)
    b = traffic.requests(mix, 151552, 2 ** 31 + 12345, 30.0)
    c = traffic.requests(mix, 151552, 7, 30.0)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


@pytest.mark.parametrize("mix", [CHAT, LONG], ids=["chat", "long"])
def test_lengths_clipped_and_same_multiset_for_every_seed(mix):
    runs = [traffic.requests(mix, 1000, seed, 30.0) for seed in (1, 2, 3)]
    for reqs in runs:
        plen = [len(r.prompt) for r in reqs]
        olen = [r.max_new_tokens for r in reqs]
        assert min(plen) >= mix["prompt"]["min"]
        assert max(plen) <= mix["prompt"]["max"]
        assert min(olen) >= mix["output"]["min"]
        assert max(olen) <= mix["output"]["max"]
        assert all(0 <= r.prompt.min() and r.prompt.max() < 1000
                   for r in reqs)
    sizes = [sorted((len(r.prompt), r.max_new_tokens) for r in reqs)
             for reqs in runs]
    assert sorted(len(r.prompt) for r in runs[0]) == \
        sorted(len(r.prompt) for r in runs[1])
    assert sizes[0] != sizes[1]           # paired in another order


@pytest.mark.parametrize("n,block", [(225, 15), (100, 15), (30, 1)])
def test_stratified_blocks_hold_one_value_of_each_band(n, block):
    """Each run of ``block`` consecutive entries takes one value from each
    band of neighbouring values; every seed keeps the same multiset."""
    values = np.arange(n) * 10
    runs = -(-n // block)
    orders = [traffic.stratified(values, block, np.random.default_rng(s))
              for s in (1, 2)]
    for out in orders:
        assert sorted(out) == list(values)
        if n % block == 0:
            for r in range(runs):
                bands = sorted(out[r * block:(r + 1) * block] // 10 // runs)
                assert bands == list(range(block))
    assert list(orders[0]) != list(orders[1])


def test_chat_blocks_spread_the_longest_prompts():
    mix = dict(CHAT, rate_per_s=5.0, block=15)
    reqs = traffic.requests(mix, 1000, 2 ** 31 + 77, 45.0)
    longest = sorted(range(len(reqs)), key=lambda i: len(reqs[i].prompt))
    assert sorted(i // 15 for i in longest[-15:]) == list(range(15))


def test_lengths_follow_the_lognormal():
    n = traffic.lengths(1001, {"median": 512, "sigma": 0.9, "min": 32,
                               "max": 2048})
    assert n[500] == 512                  # the middle quantile is the median
    assert n[0] == 32 and n[-1] == 2048   # both tails clipped
    assert list(n) == sorted(n)


def test_open_loop_due_times():
    reqs = traffic.requests(CHAT, 100, 5, 30.0)
    due = np.array([r.due_s for r in reqs])
    assert len(reqs) == round(6.5 * 30)
    assert due[0] == 0.0 and due[-1] < 30.0
    assert np.all(np.diff(due) > 0)
    # the gaps, the last one up to the window's end included, are the same
    # set for every seed: only their order moves
    other = np.array([r.due_s for r in traffic.requests(CHAT, 100, 6, 30.0)])
    gaps = [np.sort(np.append(np.diff(t), 30.0 - t[-1])) for t in (due, other)]
    assert np.allclose(gaps[0], gaps[1])


class SlowEngine:
    """Serves one token per step, each step taking ``dt`` seconds."""

    def __init__(self, dt):
        self.dt, self.reqs = dt, {}

    def submit(self, prompt, max_new_tokens):
        self.reqs[len(self.reqs)] = {"state": "DECODE", "tokens": [],
                                     "n": max_new_tokens}
        return len(self.reqs) - 1

    def step(self):
        time.sleep(self.dt)
        for r in self.reqs.values():
            if r["state"] == "DECODE":
                r["tokens"].append(1)
                if len(r["tokens"]) == r["n"]:
                    r["state"] = "DONE"

    def request_states(self):
        return self.reqs


class FakeRun:
    trace = False
    clock = staticmethod(time.perf_counter)

    @staticmethod
    def annotate(name):
        return contextlib.nullcontext()


@pytest.mark.parametrize("dt", [0.0, 0.03])
def test_due_times_do_not_depend_on_service(dt):
    """The schedule is drawn before any request is served: a slow engine
    delays submission, which shows as lateness against the due time, and
    never moves a due time."""
    import serving

    mix = dict(CHAT, rate_per_s=40.0,
               output={"median": 3, "sigma": 0.1, "min": 2, "max": 4})
    reqs = traffic.requests(mix, 100, 11, 0.5)
    due = [r.due_s for r in reqs]
    client = serving.Client(FakeRun, SlowEngine(dt))
    tracked = [serving.Tracked(r) for r in reqs]
    t0 = time.perf_counter()
    i = 0
    while i < len(tracked) or client.open:
        now = time.perf_counter()
        while i < len(tracked) and t0 + tracked[i].req.due_s <= now:
            client.submit(tracked[i], now, t0 + tracked[i].req.due_s)
            i += 1
        if client.open:
            client.step()
    assert [t.req.due_s for t in tracked] == due
    assert all(t.state == "DONE" for t in tracked)
    late = max(client.lateness)
    assert late >= 0.0 and (late >= 0.02 if dt else late < 0.02)
    # every token's arrival is stamped after its request fell due
    assert all(t.times[0] >= t.due_at == t0 + t.req.due_s for t in tracked)


def test_token_rows_distinct_and_shifted():
    t0, l0 = traffic.token_rows(4, 64, 256, 2 ** 32 + 9, 0)
    t1, _ = traffic.token_rows(4, 64, 256, 2 ** 32 + 9, 1)
    assert t0.shape == (4, 64) and np.array_equal(t0[:, 1:], l0[:, :-1])
    assert len({r.tobytes() for r in np.concatenate([t0, t1])}) == 8
    again, _ = traffic.token_rows(4, 64, 256, 2 ** 32 + 9, 0)
    assert np.array_equal(t0, again)


@pytest.mark.parametrize("q,want", [(50, 3.0), (95, 5.0), (100, 5.0),
                                    (1, 1.0)])
def test_percentile_nearest_rank(q, want):
    assert traffic.percentile([5, 1, 4, 2, 3], q) == want
