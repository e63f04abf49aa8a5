"""The traffic generator: seeded determinism, the stated distributions,
and page-granular prefix reuse between the turns of a session."""
import json
from pathlib import Path

import numpy as np

from bench.workload import ChatTraffic, quantile_lengths

MIX = json.loads((Path(__file__).resolve().parents[1] / "traffic"
                  / "chat.json").read_text())
VOCAB, MAX_LEN, PAGE = 151936, 4096, 16


def sessions(seed, n=64):
    t = ChatTraffic(MIX, seed, VOCAB, MAX_LEN)
    return t, [t.next_session(first_residual=i < 4) for i in range(n)]


def test_same_seed_same_traffic():
    (t1, a), (t2, b) = sessions(2**40 + 3), sessions(2**40 + 3)
    assert t1.system_prompts == t2.system_prompts
    assert [(s.system, s.user, s.answers) for s in a] == \
        [(s.system, s.user, s.answers) for s in b]


def test_other_seed_other_tokens_same_lengths():
    (t1, a), (t2, b) = sessions(1), sessions(2)
    assert [s.user for s in a] != [s.user for s in b]
    assert t1.system_prompts != t2.system_prompts
    assert [(s.system, [len(u) for u in s.user], s.answers) for s in a] == \
        [(s.system, [len(u) for u in s.user], s.answers) for s in b]


def test_lengths_follow_the_stated_lognormal():
    for key in ("user_tokens", "answer_tokens"):
        spec = MIX[key]
        x = quantile_lengths(spec, MIX["lengths_per_run"])
        assert x.min() >= spec["min"] and x.max() <= spec["max"]
        assert abs(np.median(x) - spec["median"]) <= 1
        # sigma of log-lengths from the quartiles, which clipping leaves
        lo, hi = np.percentile(np.log(x), [25, 75])
        assert abs((hi - lo) / 1.349 - spec["sigma"]) < 0.05 * spec["sigma"]


def test_sessions_fit_the_context_and_open_on_a_system_prompt():
    t, ss = sessions(7)
    for s in ss:
        ctx = MIX["system_prompt_tokens"]
        for u, a in zip(s.user, s.answers):
            ctx += len(u) + a
        assert ctx <= MAX_LEN
    assert {s.system for s in ss} == set(range(MIX["system_prompts"]))


def test_turns_reuse_the_previous_prompt_page_by_page():
    t, ss = sessions(11)
    s = next(s for s in ss if len(s.answers) >= 3)
    prompts = []
    for turn in range(3):
        prompts.append(s.prompt(t.system_prompts, turn))
        s.served.append(list(range(s.answers[turn])))   # as if served
    for prev, nxt in zip(prompts, prompts[1:]):
        assert nxt[:len(prev)] == prev
        # every full page of the previous prompt is a page of the next
        full = len(prev) // PAGE * PAGE
        assert full >= MIX["system_prompt_tokens"]
        assert nxt[:full] == prev[:full]
