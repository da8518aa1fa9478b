"""The generator: every seed offers the same work on the same schedule."""

import numpy as np
import pytest

from chipbench import traffic


@pytest.mark.parametrize("name", ["chat_poisson", "chat_backlog"])
def test_same_multiset_every_seed(name):
    spec = traffic.load_traffic(name)
    plans = [traffic.generate(spec, seed, 50.0, 50257, 768, 1024)
             for seed in (0, 7, 2 ** 31 + 123)]
    pairs = [sorted((r.prompt.size, r.max_new_tokens) for r in p)
             for p in plans]
    assert pairs[0] == pairs[1] == pairs[2]
    greedy = [sum(r.greedy for r in p) for p in plans]
    assert greedy[0] == greedy[1] == greedy[2] == len(plans[0]) // 2
    # other token ids, the file's own order
    assert [r.prompt.size for r in plans[0]] == \
        [r.prompt.size for r in plans[1]]
    assert not np.array_equal(plans[0][0].prompt[:8], plans[1][0].prompt[:8])
    # the same seed gives the same inputs
    again = traffic.generate(spec, 7, 50.0, 50257, 768, 1024)
    assert all(np.array_equal(a.prompt, b.prompt) and a.due_s == b.due_s
               for a, b in zip(plans[1], again))


def test_open_loop_arrivals_are_a_fixed_set_of_gaps():
    spec = traffic.load_traffic("chat_poisson")
    n = round(spec["rate_per_s"] * 50.0)
    gaps = []
    for order_seed in (0, 1):  # a second schedule is a second file
        plan = traffic.generate(dict(spec, order_seed=order_seed), 1, 50.0,
                                50257, 768, 1024)
        assert len(plan) == n
        due = np.array([r.due_s for r in plan])
        assert due[0] == 0.0 and np.all(np.diff(due) > 0) and due[-1] < 50.0
        gaps.append(np.sort(np.diff(due)))
    # the same gaps but one (the last gap closes the window, unseen)
    common = np.intersect1d(np.round(gaps[0], 9), np.round(gaps[1], 9))
    assert len(common) >= n - 3


def test_backlog_never_repeats_a_prompt():
    spec = traffic.load_traffic("chat_backlog")
    plan = traffic.generate(spec, 3, 50.0, 50257, 768, 1024)
    assert len(plan) >= 2 * spec["multiset_size"]
    heads = {tuple(r.prompt[:16]) for r in plan}
    assert len(heads) == len(plan)


def test_stratified_order_spreads_the_heavy():
    rng = np.random.RandomState(0)
    keys = list(range(64))
    order = traffic.stratified_order(keys, 8, rng)
    assert sorted(order) == keys
    for k in range(0, 64, 8):
        strata = sorted(i // 8 for i in order[k:k + 8])
        assert strata == list(range(8))


def test_ramp_is_in_flight_streams_of_the_same_mix():
    spec = traffic.load_traffic("chat_poisson")
    live = spec["ramp_live"]
    ramps = [traffic.ramp(spec, seed, 50257, 768, 1024) for seed in (1, 9)]
    assert len(ramps[0]) == live
    assert [(r.prompt.size, r.max_new_tokens) for r in ramps[0]] == \
        [(r.prompt.size, r.max_new_tokens) for r in ramps[1]]
    whole = sorted(traffic.lognormal_quantiles(spec["output_len"], live))
    left = sorted(r.max_new_tokens for r in ramps[0])
    assert all(2 <= a <= b for a, b in zip(left, whole))
    assert sum(left) < 0.7 * sum(whole)   # caught part-way through
    assert all(r.index < 0 for r in ramps[0])
    assert traffic.ramp(dict(spec, ramp_live=0), 1, 50257, 768, 1024) == []


def test_another_kind_is_a_generator_module_of_its_own(tmp_path,
                                                       monkeypatch):
    import sys

    pkg = tmp_path / "chipbench_generators_probe"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "echo.py").write_text(
        "def generate(spec, seed, seconds, vocab_size, max_prompt,"
        " max_total):\n    return [spec['kind'], seed]\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    import chipbench_generators_probe.echo as echo

    monkeypatch.setitem(sys.modules, "chipbench.generators.echo", echo)
    assert traffic.generate({"kind": "echo"}, 4, 1.0, 8, 8, 8) == ["echo", 4]


# What chat_poisson.json held before PR 33 re-placed its rate: the mix is
# the same, only the rate (and so the count of requests) moved.
OLD_CHAT_MIX = {
    "prompt_len": {"median": 192, "sigma": 0.8, "min": 32, "max": 768},
    "output_len": {"median": 64, "sigma": 0.6, "min": 16, "max": 192},
    "strata": 8, "order_seed": 0,
    "sampling_mix": [{"share": 0.5, "temperature": 0.0},
                     {"share": 0.5, "temperature": 0.7, "top_p": 0.9}],
    "slo": {"ttft_ms": 2000, "tpot_ms": 600},
}
OLD_CHAT_REQUESTS = 62   # 1.24 requests/s x 50 s


def test_chat_poisson_re_placed_keeps_its_mix():
    spec = traffic.load_traffic("chat_poisson")
    # four fifths of the knee, to the file's one decimal
    assert abs(spec["rate_per_s"] - 0.8 * spec["knee_per_s"]) <= 0.05 + 1e-9
    assert 1 <= spec["ramp_live"] <= 46
    for key, old in OLD_CHAT_MIX.items():
        assert spec[key] == old, key
    n = round(spec["rate_per_s"] * 50.0)
    assert n > 4 * OLD_CHAT_REQUESTS
    plans = [traffic.generate(spec, seed, 50.0, 50257, 768, 1024)
             for seed in (3, 2 ** 31 + 77)]
    assert len(plans[0]) == len(plans[1]) == n
    # the same schedule whatever the seed: lengths and due instants
    for a, b in zip(*plans):
        assert (a.prompt.size, a.max_new_tokens, a.due_s) == \
            (b.prompt.size, b.max_new_tokens, b.due_s)
    # the lengths are the same distributions' quantiles, cut finer: each
    # quantile the old 62 requests held is met again within a rank's step
    for which, pick in (("prompt_len", lambda r: r.prompt.size),
                        ("output_len", lambda r: r.max_new_tokens)):
        new = sorted(pick(r) for r in plans[0])
        assert new == sorted(traffic.lognormal_quantiles(spec[which], n))
        old = traffic.lognormal_quantiles(OLD_CHAT_MIX[which],
                                          OLD_CHAT_REQUESTS)
        for i, v in enumerate(old):
            lo = new[int(n * i / OLD_CHAT_REQUESTS)]
            hi = new[min(n - 1, -(-n * (i + 1) // OLD_CHAT_REQUESTS))]
            assert lo <= v <= hi, (which, i)
        assert abs(np.mean(new) / np.mean(old) - 1) < 0.01, which
    # the answers are dealt as before: exactly half greedy
    assert sum(r.greedy for r in plans[0]) == n // 2
