"""Closed-loop clients that drive ``ServingEngine.submit``/``step``/
``cancel``, one client per slot and no think time, with the benchmark's
own host timestamps of what ``step()`` returned.

``step()`` ends in a host sync of the sampled ids, so a token's timestamp
is when the host could have sent it to a user.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from bench.workload import ChatTraffic, Session


@dataclass
class Request:
    client: int
    session: Session
    turn: int
    prompt: List[int]
    answer_len: int
    submitted: float                      # host clock at submit()
    rid: Optional[int] = None
    tokens: List[int] = field(default_factory=list)
    times: List[float] = field(default_factory=list)
    done: Optional[float] = None


class Clients:
    """``n`` clients, each playing sessions from ``traffic`` one turn at a
    time. ``spans`` names each call into the engine on the profiler's
    clock (``jax.profiler.TraceAnnotation``) when set."""

    def __init__(self, eng, traffic: ChatTraffic, n: int, spans=None):
        self.eng = eng
        self.traffic = traffic
        self.span = spans or contextlib.nullcontext
        self.sessions = [traffic.next_session(first_residual=True)
                         for _ in range(n)]
        self.turns = [0] * n
        self.idle = list(range(n))          # clients with nothing in flight
        self.live: Dict[int, Request] = {}  # rid -> request
        self.finished: List[Request] = []
        self.all: List[Request] = []
        self.step_s: List[float] = []       # host time of each step() call
        self.step_at: List[float] = []      # host clock at each step() end
        self.step_prefill: List[int] = []   # prompt tokens each step ran
        self.submit_open = True

    def _next_prompt(self, c: int):
        s, t = self.sessions[c], self.turns[c]
        if t == len(s.answers):
            s = self.sessions[c] = self.traffic.next_session()
            t = self.turns[c] = 0
        return s, t, s.prompt(self.traffic.system_prompts, t)

    def submit_idle(self) -> None:
        if not self.submit_open:
            return
        waiting = []
        for c in self.idle:
            s, t, prompt = self._next_prompt(c)
            now = time.perf_counter()
            with self.span("submit"):
                rid = self.eng.submit(prompt)
            if rid is None:                 # no slot or pages: try again
                waiting.append(c)
                continue
            req = Request(c, s, t, prompt, s.answers[t], now, rid)
            self.live[rid] = req
            self.all.append(req)
        self.idle = waiting

    def step(self) -> int:
        """One engine step; returns the tokens it reported."""
        prefilled = self.eng.prefill_tokens
        t0 = time.perf_counter()
        with self.span("step"):
            out = self.eng.step()
        now = time.perf_counter()
        self.step_s.append(now - t0)
        self.step_at.append(now)
        self.step_prefill.append(self.eng.prefill_tokens - prefilled)
        for rid, tok in out.items():
            req = self.live[rid]
            req.tokens.append(int(tok))
            req.times.append(now)
            if len(req.tokens) == req.answer_len:
                with self.span("cancel"):
                    self.eng.cancel(rid)
                req.done = now
                del self.live[rid]
                self.finished.append(req)
                req.session.served.append(req.tokens)
                self.turns[req.client] += 1
                self.idle.append(req.client)
        return len(out)

    def run_until(self, deadline: float) -> None:
        while time.perf_counter() < deadline:
            with self.span("client"):
                self.submit_idle()
            self.step()

    def run_steps(self, n: int) -> None:
        for _ in range(n):
            self.submit_idle()
            self.step()


def percentile(values, q: float) -> Optional[float]:
    """The ``q``-th percentile (linear interpolation), None when empty."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))

