"""The traffic generator: one seed gives one schedule, seeds replay the
mix's timeline with other prompts, and every request is due inside the
window."""

import json

import numpy as np
import pytest

import harness

MIXES = sorted(p.stem for p in (harness.BENCH / "traffic").glob("*.json"))


def load(name):
    mix = json.loads((harness.BENCH / "traffic" / f"{name}.json").read_text())
    return mix, harness.traffic_module(mix)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    mix, gen = load(name)
    a = gen.schedule(mix, 2**31 + 5, 50, 49152)
    b = gen.schedule(mix, 2**31 + 5, 50, 49152)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x["due"] == y["due"] and x["tenant"] == y["tenant"]
        assert x["entry"] == y["entry"]
        assert x["new_tokens"] == y["new_tokens"]
        assert np.array_equal(x["prompt"], y["prompt"])


@pytest.mark.parametrize("name", MIXES)
def test_seeds_differ_in_prompts_not_in_work(name):
    mix, gen = load(name)
    a = gen.schedule(mix, 1, 50, 49152)
    b = gen.schedule(mix, 2, 50, 49152)
    for x, y in zip(a, b):
        assert (x["due"], x["tenant"], x["entry"], x["new_tokens"]) == \
            (y["due"], y["tenant"], y["entry"], y["new_tokens"])
    assert all(not np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, b))
    assert len(a) == len(b)


@pytest.mark.parametrize("name", MIXES)
def test_schedule_shape(name):
    mix, gen = load(name)
    s = gen.schedule(mix, 3, 50, 49152)
    assert len(s) == round(mix["rate"] * 50)
    assert all(0 <= r["due"] < 50 for r in s)
    assert all(b["due"] >= a["due"] for a, b in zip(s, s[1:]))
    lens = [r["new_tokens"] for r in s]
    spec = mix["new_tokens"]
    assert spec["min"] <= min(lens) and max(lens) <= spec["max"]
    assert abs(np.median(lens) - spec["median"]) <= 2
    assert sum(r["entry"] == "score" for r in s) >= 1
    assert all(r["prompt"].shape == (mix["prompt_len"],) for r in s)
    tenants = {r["tenant"] for r in s}
    assert tenants <= set(range(mix["tenants"]))
    if mix["tenants"] > 1:
        switches = sum(a["tenant"] != b["tenant"] for a, b in zip(s, s[1:]))
        assert switches >= 50 / mix["switch_mean_s"] / 2
    else:
        assert tenants == {0}


def test_profile_sample_is_the_mix_without_rare_entries():
    mix, gen = load("warm1")
    s = gen.profile_sample(mix, 4, 49152)
    assert len(s) == mix["profile_requests"]
    assert {r["entry"] for r in s} == {"generate"}

