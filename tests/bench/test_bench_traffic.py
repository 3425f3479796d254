"""The traffic generator: deterministic from the seed, and every seed
draws the same work in another order."""

import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402
from bench import harness, traffic  # noqa: E402

SEEDS = [0, 7, 2**31 + 12345, 2**40 + 3]
MIXES = {"cold_burst": traffic.load_mix("cold_burst"), "toy_warm": bench_tiny.MIX,
         "steady_chat": traffic.load_mix("steady_chat")}


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_same_seed_same_requests(mix):
    m = MIXES[mix]
    a = traffic.schedule(m, SEEDS[2], 51, 32768)
    b = traffic.schedule(m, SEEDS[2], 51, 32768)
    assert [r.due for r in a] == [r.due for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) and x.out_len == y.out_len for x, y in zip(a, b))


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_every_seed_draws_the_same_work(mix):
    m = MIXES[mix]
    runs = [traffic.schedule(m, s, 51, 32768) for s in SEEDS]
    prompts = [Counter(len(r.prompt) for r in reqs) for reqs in runs]
    outs = [Counter(r.out_len for r in reqs) for reqs in runs]
    assert all(p == prompts[0] for p in prompts) and all(o == outs[0] for o in outs)
    gaps = [sorted(np.round(np.diff([r.due for r in reqs]), 9)) for reqs in runs]
    assert all(g == gaps[0] for g in gaps)
    firsts = [[tuple(r.prompt[:4]) for r in reqs[:4]] for reqs in runs]
    assert len({tuple(f) for f in firsts}) == len(SEEDS)  # the tokens do differ
    for reqs in runs:
        assert all(0 <= r.due < 51 for r in reqs)
        assert all(len(r.prompt) in m["prompt_lens"] for r in reqs)
        assert all(m["output_min"] <= r.out_len <= m["output_max"] for r in reqs)


def test_cold_burst_opens_with_the_burst_and_fixed_first_round():
    """The first request is due as the window opens; those due while the
    replica starts are the burst, and the first round of 16 admitted
    holds the same lengths on every seed."""
    m = traffic.load_mix("cold_burst")
    for s in SEEDS:
        reqs = traffic.schedule(m, s, 51, 32768)
        assert reqs[0].due == 0.0
        assert len(reqs) == round(m["rate_rps"] * 51)
        first = Counter(len(r.prompt) for r in reqs[: m["slots"]])
        assert first == Counter({128: 1, 512: 5, 1024: 5, 2048: 5})


def test_prompt_tokens_follow_the_profiled_zipf_distribution():
    """Prompt tokens are drawn as the ``stats`` plan's profile draws its
    text: Zipfian, the most frequent id first."""
    m = traffic.load_mix("cold_burst")
    toks = np.concatenate([r.prompt for r in traffic.schedule(m, 3, 51, 32768)])
    assert toks.min() >= 0 and toks.max() < 32768
    hot = np.mean(toks < 8192)  # the quarter of the rows the plan keeps resident
    assert 0.9 < hot < 0.96, hot  # Zipf s=1.1: 1 - 0.0746
    assert np.mean(toks == 0) > np.mean(toks == 1) > np.mean(toks == 100)


def test_every_first_round_group_is_warmed():
    """Whatever number of the first requests is due when the replica is up,
    the groups the first admission forms of them were compiled in set-up."""
    m = traffic.load_mix("cold_burst")
    for s in SEEDS:
        reqs = traffic.schedule(m, s, 51, 32768)
        warmed = set(harness.warm_groups(m, reqs))
        for k in range(1, m["slots"] + 1):
            for S, g in Counter(len(r.prompt) for r in reqs[:k]).items():
                assert (g, S) in warmed
