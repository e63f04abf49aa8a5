"""The one traffic generator: multi-turn chat sessions, from a mix file's
parameters and a seed.

A mix file (``bench/traffic/<name>.json``) states the session shape:

- ``system_prompts`` distinct system prompts of ``system_prompt_tokens``
  tokens; each session starts from one of them;
- each turn's prompt is the previous prompt, plus the previous answer as it
  was served, plus a new user message of ``user_tokens`` tokens;
- each answer is ``answer_tokens`` long (the client cancels the request
  once it has that many);
- a session ends when its next turn would pass the configuration's
  ``max_len``; the client then starts the next session.

Lengths are lognormal (``median``, ``sigma``), clipped to ``[min, max]``:
the lognormal's quantiles at evenly spaced probabilities, in an order
shuffled by the mix's own ``lengths_seed``. Every run seed gets the same
lengths in the same order and draws only the token ids (and the weights).
A closed loop over the same lengths makes the same sequence of engine
calls, so runs differ by what the chip and host do, not by their work.

Each client's first session starts with a residual answer: a uniform
fraction of its drawn length, as if the answer were already under way when
the run began. So the clients do not all finish their first turn together
and the measured window opens on a desynchronised batch.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass, field
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List

import numpy as np


def load_mix(root: Path, name: str) -> Dict:
    return json.loads((root / "bench" / "traffic" / f"{name}.json")
                      .read_text())


def quantile_lengths(spec: Dict, n: int) -> np.ndarray:
    """``n`` lengths at the lognormal's quantiles ``(i + 0.5) / n``,
    clipped: the same multiset for every seed."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


@dataclass
class Session:
    system: int
    user: List[List[int]]          # user messages, one per turn
    answers: List[int]             # answer lengths, one per turn
    served: List[List[int]] = field(default_factory=list)

    def prompt(self, system_prompts: List[List[int]], turn: int
               ) -> List[int]:
        out = list(system_prompts[self.system])
        for t in range(turn):
            out += self.user[t] + self.served[t]
        return out + self.user[turn]


class ChatTraffic:
    """Sessions for one run, deterministic in the seed. ``next_session``
    hands them out in order; each client plays one at a time."""

    def __init__(self, mix: Dict, seed: int, vocab: int, max_len: int):
        self.mix = mix
        self.max_len = max_len
        lengths = np.random.default_rng(mix["lengths_seed"])
        n = mix["lengths_per_run"]
        self.user_lens = lengths.permutation(quantile_lengths(
            mix["user_tokens"], n))
        self.answer_lens = lengths.permutation(quantile_lengths(
            mix["answer_tokens"], n))
        self._fractions = lengths
        rng = np.random.default_rng(seed)
        self.system_prompts = [
            rng.integers(0, vocab, mix["system_prompt_tokens"]).tolist()
            for _ in range(mix["system_prompts"])]
        self.vocab = vocab
        self._rng = rng
        self._next_len = 0
        self._sessions = 0

    def _take(self) -> tuple:
        i = self._next_len % len(self.user_lens)
        self._next_len += 1
        return int(self.user_lens[i]), int(self.answer_lens[i])

    def next_session(self, first_residual: bool = False) -> Session:
        """The next session, whose turns all fit ``max_len``. With
        ``first_residual`` its first answer is a residual length."""
        system = self._sessions % len(self.system_prompts)
        self._sessions += 1
        ctx = len(self.system_prompts[system])
        user, answers = [], []
        while True:
            u, a = self._take()
            if first_residual and not answers:
                a = max(1, int(math.ceil(a * self._fractions.uniform())))
            if ctx + u + a > self.max_len:
                # the pair goes back for the next session's first turn
                self._next_len -= 1
                break
            user.append(self._rng.integers(0, self.vocab, u).tolist())
            answers.append(a)
            ctx += u + a
        if not answers:
            raise ValueError("a turn does not fit max_len even in a fresh "
                             "session; the mix's lengths are too long")
        return Session(system, user, answers)
