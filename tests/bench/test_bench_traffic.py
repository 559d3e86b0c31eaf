"""The traffic generator: deterministic per seed, inside its file's
parameters, the same work for every seed."""
import math

import pytest

from bench import traffic

MIXES = ("decode_heavy", "prefill_heavy")
BIG_SEED = 2 ** 31 + 987654321      # wider than 32 signed bits


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = traffic.load(name)
    assert traffic.call(mix, BIG_SEED, 3, 49152) \
        == traffic.call(mix, BIG_SEED, 3, 49152)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_and_calls_differ_in_content_not_in_work(name):
    """Every seed and call queues the same set of sizes, in an order and
    with contents of its own."""
    mix = traffic.load(name)
    a = traffic.call(mix, 1, 0, 49152)
    b = traffic.call(mix, 2, 0, 49152)
    c = traffic.call(mix, 1, 1, 49152)

    def shape(reqs):
        return [(len(r.prompt), r.max_new_tokens) for r in reqs]

    assert sorted(shape(a)) == sorted(shape(b)) == sorted(shape(c)) \
        == sorted(traffic.sizes(mix))
    assert shape(a) != shape(b) and shape(a) != shape(c)
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in c]


@pytest.mark.parametrize("name", MIXES)
def test_requests_stay_inside_the_file(name):
    mix = traffic.load(name)
    reqs = traffic.call(mix, BIG_SEED, 0, 49152)
    assert len(reqs) == mix["requests_per_call"]
    p, n = mix["prompt_len"], mix["new_tokens"]
    for r in reqs:
        assert p["min"] <= len(r.prompt) <= p["max"]
        assert n["min"] <= r.max_new_tokens <= n["max"]
        assert len(r.prompt) + r.max_new_tokens <= mix["slot_tokens"]
        assert all(1 <= t < 49152 for t in r.prompt)
        assert 0 <= r.seed < 2 ** 31


def test_quantiles_follow_the_distribution():
    u = traffic.quantiles({"dist": "uniform", "min": 33, "max": 64}, 64)
    assert sorted(u) == sorted(list(range(33, 65)) * 2)
    lg = traffic.quantiles({"dist": "log_uniform", "min": 64, "max": 512},
                           64)
    assert min(lg) >= 64 and max(lg) <= 512
    # the median of log-uniform(64, 512) is its geometric mean, 181
    assert abs(sorted(lg)[32] - math.sqrt(64 * 512)) < 8


def test_warmup_covers_each_bucket_once():
    mix = traffic.load("decode_heavy")
    reqs = traffic.warmup(mix, 5, 49152, lambda n: 64 if n <= 64 else 128)
    assert [(len(r.prompt), r.max_new_tokens) for r in reqs] == [(64, 2)]
    split = traffic.warmup(mix, 5, 49152, lambda n: 32 if n <= 48 else 64)
    assert [len(r.prompt) for r in split] == [48, 64]


def test_shared_prefix_is_shared():
    mix = dict(traffic.load("decode_heavy"), shared_prefix=16)
    reqs = traffic.call(mix, 9, 0, 49152)
    assert len({tuple(r.prompt[:16]) for r in reqs}) == 1
    assert len({tuple(r.prompt[16:24]) for r in reqs}) == len(reqs)


def test_weight_seed_takes_any_seed():
    s = traffic.weight_seed(BIG_SEED)
    assert 0 <= s < 2 ** 31 and s == traffic.weight_seed(BIG_SEED)
    assert s != traffic.weight_seed(BIG_SEED + 1)


@pytest.mark.parametrize("bad", [{"slot_tokens": 100},
                                 {"shared_prefix": 40},
                                 {"prompt_len": {"dist": "zipf", "min": 1,
                                                 "max": 2}},
                                 {"burst": 3}])
def test_bad_mix_is_refused(tmp_path, bad):
    import json

    mix = dict(traffic.load("decode_heavy"), **bad)
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "bad.json").write_text(json.dumps(mix))
    with pytest.raises(ValueError):
        traffic.load("bad", tmp_path)
