"""The decode traffic generator: a seed repeats, every seed does the same work."""

import collections
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from perfbench import traffic  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mix():
    with open(os.path.join(HERE, "traffic", "decode_chat.json")) as f:
        return json.load(f)


def test_a_seed_reproduces_its_request_list():
    t = mix()
    assert traffic.decode_requests(t, 2**31 + 12345, 32768) == traffic.decode_requests(t, 2**31 + 12345, 32768)
    assert traffic.decode_requests(t, 1, 32768) != traffic.decode_requests(t, 2, 32768)


def test_lengths_stay_inside_the_clips():
    t = mix()
    reqs = traffic.decode_requests(t, 7, 32768)
    assert len(reqs) == t["requests"]
    assert all(t["prompt_tokens"]["min"] <= len(r["prompt"]) <= t["prompt_tokens"]["max"] for r in reqs)
    assert all(t["max_new"]["min"] <= r["max_new"] <= t["max_new"]["max"] for r in reqs)
    assert all(0 <= tok < 32768 for r in reqs for tok in r["prompt"])
    serve = t["serve"]
    assert t["prompt_tokens"]["max"] + t["max_new"]["max"] <= serve["max_seq"]


def test_every_seed_gets_the_same_lengths_cycle_by_cycle():
    t = mix()
    a, b = traffic.decode_requests(t, 3, 32768), traffic.decode_requests(t, 4, 32768)
    n = t["cycle"]
    for lo in range(0, len(a) - n + 1, n):
        for key in (lambda r: len(r["prompt"]), lambda r: r["max_new"]):
            assert collections.Counter(map(key, a[lo:lo + n])) == collections.Counter(map(key, b[lo:lo + n]))


def test_the_length_set_follows_the_distribution():
    t = mix()
    prompts = traffic.length_set(t["prompt_tokens"], t["cycle"])
    assert prompts == sorted(prompts)
    mid = prompts[len(prompts) // 2 - 1: len(prompts) // 2 + 1]
    assert mid[0] <= t["prompt_tokens"]["median"] <= mid[1]
    assert t["prompt_tokens"]["min"] <= prompts[0] and prompts[-1] <= t["prompt_tokens"]["max"]
