"""The comparison that decides ``correct``.

Once the window has closed, a sample of what it served, drawn from the
seed, is run through the plain reference: the longest request the window
finished, then others it finished or left running, until the sample holds
some hundreds of served tokens. They go through the plain reference
(``bench/reference/<model_type>.py``): each prompt with the tokens that
were served for it, in one float32 forward, over weights made again from
the seed in the placement the program had (sharded over the cell's chips
where it has more than one). For every served token the reference gives
the gap by which that token's logit lies below the reference's best logit
at the same position (0 where the served token is the reference's own
greedy choice). Two numbers are read, in logit units:

- ``mean_gap``: the mean gap over the sample's served tokens;
- ``max_gap``: the widest gap over the sample.

A configuration file's ``correct`` names the numbers compared and their
limits; ``PERF.md`` gives the readings each limit was set from. At the
published widths only ``mean_gap`` separates the program from its int8
control by the factor of three a limit needs (the widest gap of bf16 and
of int8 both come from the same few near-ties), so it is the one compared
there; ``max_gap`` is printed beside it.
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import load_module


def sample(finished: Sequence, running: Sequence, seed: int,
           min_tokens: int, max_requests: int) -> List:
    """The longest finished request, then others drawn from ``seed`` among
    the finished and those still running at the close (with the tokens
    they were served), until ``min_tokens`` served tokens are in or
    ``max_requests`` are taken."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in list(finished) + list(running) if r is not longest]
    order = np.random.default_rng(seed).permutation(len(rest))
    picked = [longest]
    for i in order:
        if (sum(len(r.tokens) for r in picked) >= min_tokens
                or len(picked) >= max_requests):
            break
        picked.append(rest[i])
    return picked


class Reference:
    """The reference forward of one configuration, compiled once for
    sequences padded to ``max_len`` and answers padded to ``max_answer``."""

    def __init__(self, root: Path, raw: Dict, max_len: int,
                 max_answer: int):
        mod = load_module(root / "bench" / "reference"
                          / f"{raw['model_type']}.py")
        self.max_len, self.max_answer = max_len, max_answer

        @jax.jit
        def gaps(w, tokens, rows, served):
            """tokens (max_len,), rows (A,) positions whose next token was
            served, served (A,) → gaps (A,)."""
            lg = mod.logits(w, mod.hidden(w, raw, tokens)[rows])
            return lg.max(-1) - jnp.take_along_axis(lg, served[:, None],
                                                    1)[:, 0]

        self._gaps = gaps

    def token_gaps(self, w, prompt: List[int], served: List[int]):
        n = len(served)
        seq = (list(prompt) + list(served))[: self.max_len]
        tokens = np.zeros(self.max_len, np.int32)
        tokens[: len(seq)] = seq
        rows = np.zeros(self.max_answer, np.int32)
        rows[:n] = len(prompt) - 1 + np.arange(n)
        srv = np.zeros(self.max_answer, np.int32)
        srv[:n] = served
        out = self._gaps(w, jnp.asarray(tokens), jnp.asarray(rows),
                         jnp.asarray(srv))
        return np.asarray(out)[:n]


def _numbers(gaps: np.ndarray) -> Dict[str, float]:
    if gaps.size == 0 or not np.isfinite(gaps).all():
        return {"max_gap": float("inf"), "mean_gap": float("inf")}
    return {"max_gap": float(gaps.max()), "mean_gap": float(gaps.mean())}


def readings(ref: Reference, w, requests: Sequence) -> Dict:
    """The compared numbers over the sample."""
    per = [ref.token_gaps(w, r.prompt, r.tokens) for r in requests]
    gaps = np.concatenate(per) if per else np.zeros(0)
    return {**_numbers(gaps), "tokens": int(gaps.size)}


def judge(read: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """Each compared number beside its limit, and whether all hold."""
    check = {k: {"value": read[k], "limit": limits[k]} for k in limits}
    ok = all(v["value"] <= v["limit"] for v in check.values())
    return {"correct": bool(ok), "check": check}
