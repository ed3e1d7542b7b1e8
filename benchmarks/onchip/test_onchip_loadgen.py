"""The traffic generator: the same seed gives the same requests, every
seed the same sizes (an open loop the same schedule), and lengths,
arrivals, prefix sharing and the Zipf split stay within the mix's
parameters."""
from collections import Counter

import numpy as np

from onchip import loadgen

BIG_SEED = 2 ** 33 + 12345


def _key(t):
    return [(r.prompt, r.max_new_tokens, r.due_s, r.client, r.prefix)
            for r in t.requests]


def _mix(name, **kw):
    mix = loadgen.load_mix(name)
    mix.update(kw)
    return mix


def test_same_seed_same_requests_other_seed_same_sizes():
    for mix in (_mix("reason"), _mix("code_shared", rate_per_s=3.0)):
        a = loadgen.generate(mix, BIG_SEED, 40, 1000, 16)
        b = loadgen.generate(mix, BIG_SEED, 40, 1000, 16)
        c = loadgen.generate(mix, 7, 40, 1000, 16)
        assert _key(a) == _key(b)
        assert _key(a) != _key(c)
        sizes = [sorted((len(r.prompt), r.max_new_tokens) for r in t.requests)
                 for t in (a, c)]
        assert sizes[0] == sizes[1]
        if mix["loop"] == "open":
            # an open loop's schedule is the same for every seed
            assert ([(r.due_s, len(r.prompt), r.max_new_tokens, r.prefix)
                     for r in a.requests]
                    == [(r.due_s, len(r.prompt), r.max_new_tokens, r.prefix)
                        for r in c.requests])


def test_closed_loop_lengths_and_staggered_start():
    mix = _mix("reason")
    t = loadgen.generate(mix, BIG_SEED, 30, 1000, 16)
    p, o = mix["prompt_len"], mix["output_len"]
    assert len(t.requests) == 16 * 48 + 16
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in t.requests)
    later = [r for r in t.requests if r.index >= 16]
    assert all(o["min"] <= r.max_new_tokens <= o["max"] for r in later)
    assert 0.8 * p["median"] < np.median([len(r.prompt) for r in later]) \
        < 1.25 * p["median"]
    first = [t.client_queue(c)[0].max_new_tokens for c in range(16)]
    assert len(set(first)) > 8 and min(first) >= 1
    assert max(first) <= o["max"]
    assert all(r.due_s is None for r in t.requests)


def test_open_loop_arrivals_prefixes_and_zipf_split():
    mix = _mix("code_shared", rate_per_s=4.0)
    t = loadgen.generate(mix, BIG_SEED, 500, 1000, 16)
    pfx = mix["prefixes"]
    assert len(t.requests) == 2000
    due = [r.due_s for r in t.requests]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 500
    assert len(t.prefixes) == pfx["count"]
    assert all(len(p) == pfx["len"] for p in t.prefixes)
    s = mix["suffix_len"]
    for r in t.requests:
        assert r.prompt[:pfx["len"]] == t.prefixes[r.prefix]
        assert s["min"] <= len(r.prompt) - pfx["len"] <= s["max"]
    w = np.arange(1, pfx["count"] + 1) ** -pfx["zipf_s"]
    want = w / w.sum()
    got = Counter(r.prefix for r in t.requests)
    for k in range(pfx["count"]):
        assert abs(got[k] / 2000 - want[k]) < 0.03
