"""The traffic generator: deterministic per seed, sizes and rates as the
mix files state them."""
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec, traffic  # noqa: E402

MIXES = sorted(p.stem for p in (ROOT / "bench" / "traffic").glob("*.json"))
BIG_SEED = 2**33 + 12345


def mix(name):
    return spec.load_traffic(name)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = traffic.generate(mix(name), BIG_SEED, 30, 1000)
    b = traffic.generate(mix(name), BIG_SEED, 30, 1000)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x["due"] == y["due"] and x["max_new"] == y["max_new"]
        assert np.array_equal(x["prompt"], y["prompt"])


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_sizes_in_another_order(name):
    """Every seed gets the same schedule -- due times, lengths and backlog
    order alike -- and other token ids; a longer window extends it."""
    m = mix(name)
    a = traffic.generate(m, 1, 30, 1000)
    b = traffic.generate(m, BIG_SEED, 30, 1000)
    c = traffic.generate(m, 2, 45, 1000)
    assert [(r["due"], len(r["prompt"]), r["max_new"]) for r in a] == \
        [(r["due"], len(r["prompt"]), r["max_new"]) for r in b] == \
        [(r["due"], len(r["prompt"]), r["max_new"]) for r in c[:len(a)]]
    assert any(not np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, b))
    block = m["block"]
    full = len(a) // block * block
    assert sorted(len(r["prompt"]) for r in a[:block]) == \
        sorted(len(r["prompt"]) for r in a[block:2 * block])
    assert [len(r["prompt"]) for r in a[:block]] != \
        [len(r["prompt"]) for r in a[block:2 * block]]
    assert full >= 2 * block


@pytest.mark.parametrize("name", MIXES)
def test_lengths_follow_the_file(name):
    m = mix(name)
    reqs = traffic.generate(m, 7, 30, 1000)
    for key, got in (("prompt", [len(r["prompt"]) for r in reqs]),
                     ("output", [r["max_new"] for r in reqs])):
        d = m[key]
        assert min(got) >= d["min"] and max(got) <= d["max"]
        assert abs(np.median(got) / d["median"] - 1) < 0.1
    assert all(0 <= r["prompt"].min() and r["prompt"].max() < 1000
               for r in reqs)


@pytest.mark.parametrize("name", [n for n in MIXES
                                  if mix(n)["kind"] == "open_poisson"])
def test_open_loop_rate_follows_the_file(name):
    m = mix(name)
    reqs = traffic.generate(m, 3, 40, 1000)
    horizon = m["ramp_s"] + 40 + m["drain_s"]
    assert len(reqs) == int(np.ceil(m["rate_rps"] * horizon))
    dues = np.array([r["due"] for r in reqs])
    assert np.all(np.diff(dues) >= 0)
    assert abs(len(reqs) / dues[-1] / m["rate_rps"] - 1) < 0.05


def test_backlog_is_due_at_once():
    m = mix("batch")
    reqs = traffic.generate(m, 3, 40, 1000)
    assert len(reqs) == m["requests"]
    assert all(r["due"] == 0.0 for r in reqs)


def test_seed_range():
    traffic.seed_words(2**40 + 3)
    with pytest.raises(ValueError):
        traffic.seed_words(-1)
