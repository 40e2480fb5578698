"""The generator gives every seed the same work: the same multiset of
lengths and the same number of arrivals, at different instants."""

import json
import os

import numpy as np
import pytest

from benchlib import spec, traffic

TRAFFIC = os.path.join(spec.BENCH_DIR, "traffic")
SEEDS = (0, 7, 2 ** 31 + 12345)


def load(name):
    return spec.load_json(os.path.join(TRAFFIC, name + ".json"))


def pairs_of(reqs):
    return sorted((len(r["prompt_ids"]), r["max_tokens"]) for r in reqs)


@pytest.mark.parametrize("seconds", [25, 45])
def test_open_loop_same_work_every_seed(seconds):
    mix = load("chat_open")
    scheds = [traffic.open_loop_schedule(mix, seconds, 65024, s)
              for s in SEEDS]
    n_win = round(mix["rate_per_s"] * seconds)
    n_ramp = round(mix["rate_per_s"] * mix["ramp_seconds"])
    for sc in scheds:
        measured = [r for r in sc["requests"] if r["measured"]]
        assert len(measured) == n_win
        assert len(sc["requests"]) == n_win + n_ramp
        lo, hi = sc["window"]
        assert all(lo <= r["due"] < hi for r in measured)
        assert all(r["due"] < lo for r in sc["requests"]
                   if not r["measured"])
    first = [r for r in scheds[0]["requests"] if r["measured"]]
    for sc in scheds[1:]:
        other = [r for r in sc["requests"] if r["measured"]]
        assert pairs_of(other) == pairs_of(first)          # same multiset
        assert [r["due"] for r in other] != [r["due"] for r in first]
        assert [r["prompt_ids"] for r in other] \
            != [r["prompt_ids"] for r in first]
    # Frozen episodes: the same arrival offsets inside the 5-s episodes,
    # whatever the seed; only their order differs.
    width = mix["stratum_seconds"]

    def offsets(reqs, lo):
        return sorted(round((r["due"] - lo) % width, 9) for r in reqs)

    lo = scheds[0]["window"][0]
    assert all(offsets([r for r in sc["requests"] if r["measured"]], lo)
               == offsets(first, lo) for sc in scheds[1:])
    # The same seed gives the same inputs.
    again = traffic.open_loop_schedule(mix, seconds, 65024, SEEDS[1])
    assert again == scheds[1]


def test_open_loop_lengths_follow_the_mix():
    mix = load("chat_open")
    reqs = traffic.open_loop_schedule(mix, 45, 65024, 1)["requests"]
    prompts = [len(r["prompt_ids"]) for r in reqs if r["measured"]]
    outs = [r["max_tokens"] for r in reqs if r["measured"]]
    p, o = mix["lengths"]["prompt"], mix["lengths"]["output"]
    assert p["min"] <= min(prompts) and max(prompts) <= p["max"]
    assert o["min"] <= min(outs) and max(outs) <= o["max"]
    assert abs(np.median(prompts) - p["median"]) <= 4
    assert abs(np.median(outs) - o["median"]) <= 2
    srv = mix["server_params"]
    assert max(a + b for a, b in zip(prompts, outs)) < srv["max_seq_len"] \
        or p["max"] + o["max"] <= srv["max_seq_len"]


def test_closed_loop_blocks_are_balanced_and_seed_free():
    mix = load("doc_flood")
    lists = [traffic.closed_loop_list(mix, 65024, s) for s in SEEDS]
    assert all(pairs_of(x) == pairs_of(lists[0]) for x in lists)
    block = mix["block"]
    p = mix["lengths"]["prompt"]
    mid = (p["min"] + p["max"]) / 2
    for reqs in lists:
        for b in range(0, 20 * block, block):   # any prefix is balanced
            chunk = [len(r["prompt_ids"]) for r in reqs[b:b + block]]
            assert abs(np.mean(chunk) - mid) < 0.05 * mid
    assert [len(r["prompt_ids"]) for r in lists[0][:64]] \
        != [len(r["prompt_ids"]) for r in lists[1][:64]]
    srv = mix["server_params"]
    assert p["max"] + mix["lengths"]["output"]["max"] < srv["max_seq_len"]


def test_train_documents_same_lengths_every_seed():
    mix = load("lora_packed")
    docs = [traffic.train_documents(mix, 65024, s) for s in SEEDS[:2]]
    assert sorted(map(len, docs[0])) == sorted(map(len, docs[1]))
    assert list(map(len, docs[0])) != list(map(len, docs[1]))
    assert docs[0] != docs[1]


def test_pack_rows_is_the_trainers_packing():
    from runbooks_tpu.train.data import pack_documents

    mix = load("lora_packed")
    docs = traffic.train_documents(mix, 65024, 3)[:80]
    mine = list(traffic.pack_rows(docs, 256))
    theirs = list(pack_documents(docs, 256))
    assert len(mine) == len(theirs) > 10
    for a, b in zip(mine, theirs):
        for key in b:
            assert np.array_equal(a[key], b[key]), key
