"""The traffic generator: determinism in the seed, lengths inside their
buckets, and the same work for every seed."""
import collections

import numpy as np
import pytest

from harness import spec, traffic

BIG_SEED = 2 ** 33 + 12345
# open-loop mixes no cell runs yet, as a mix file would give them
OPEN_LOOP = {
    "chat": dict(
        name="chat", slots_per_chip=32, prompt_buckets=[256, 512, 1024, 2048],
        new_token_bucket=512,
        prompt_len=dict(dist="lognormal", median=512, sigma=0.8, min=128,
                        max=2048),
        output_len=dict(dist="lognormal", median=128, sigma=0.7, min=32,
                        max=512),
        arrivals=dict(kind="poisson", rate_rps=3.0), warm_s=10),
    "complete": dict(
        name="complete", slots_per_chip=16, prompt_buckets=[1024, 2048, 3968],
        new_token_bucket=128,
        prompt_len=dict(dist="lognormal", median=2048, sigma=0.5, min=512,
                        max=3968),
        output_len=dict(dist="lognormal", median=48, sigma=0.6, min=16,
                        max=128),
        arrivals=dict(kind="gamma", cv=2.0, rate_rps=4.0), warm_s=6),
}
MIXES = ("batch",) + tuple(OPEN_LOOP)


def _mix(name):
    if name in OPEN_LOOP:
        traffic.check(OPEN_LOOP[name])
        return OPEN_LOOP[name]
    return spec.traffic(name)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_plan(name):
    mix = _mix(name)
    a, b = traffic.Plan(mix, BIG_SEED, 1000), traffic.Plan(mix, BIG_SEED, 1000)
    for _ in range(150):
        x, y = a.take(), b.take()
        assert x.due == y.due and x.max_new == y.max_new
        assert np.array_equal(x.tokens, y.tokens)
    c = traffic.Plan(mix, BIG_SEED + 1, 1000)
    assert any(not np.array_equal(c.take().tokens, a.take().tokens)
               for _ in range(5))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_fit_buckets(name):
    mix = _mix(name)
    plan = traffic.Plan(mix, 7, 50)
    for _ in range(3 * traffic.POOL):
        r = plan.take()
        assert mix["prompt_len"]["min"] <= len(r.tokens) <= max(mix["prompt_buckets"])
        assert mix["output_len"]["min"] <= r.max_new <= mix["new_token_bucket"]
        assert r.tokens.min() >= 0 and r.tokens.max() < 50


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_same_block(name):
    """Each block of POOL requests serves the same lengths and gaps, in a
    seed's own order."""
    mix = _mix(name)
    blocks = []
    for seed in (1, 2, BIG_SEED):
        plan = traffic.Plan(mix, seed, 1000)
        reqs = [plan.take() for _ in range(traffic.POOL)]
        gaps = np.diff([0.0] + [r.due for r in reqs]) if not plan.backlog else []
        blocks.append((collections.Counter(len(r.tokens) for r in reqs),
                       collections.Counter(r.max_new for r in reqs),
                       sorted(np.round(gaps, 9)), [len(r.tokens) for r in reqs]))
    assert blocks[0][:3] == blocks[1][:3] == blocks[2][:3]
    assert blocks[0][3] != blocks[1][3]


def test_rates_and_medians():
    chat = traffic.Plan(_mix("chat"), 5, 1000)
    assert chat.gaps.mean() == pytest.approx(1 / _mix("chat")["arrivals"]["rate_rps"])
    assert np.median(chat.prompt_lens) == pytest.approx(512, rel=0.05)
    burst = traffic.gaps({"kind": "gamma", "cv": 2.0, "rate_rps": 4.0})
    assert burst.std() / burst.mean() > 1.5          # bursty: CV near 2
