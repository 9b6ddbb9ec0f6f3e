"""The one traffic generator: closed-loop multi-turn sessions.

A mix file (``bench/traffic/<mix>.json``) holds only parameters; this
module turns them into the sizes and tokens a run serves. ``clients``
callers each keep one request in flight; a request is one turn of a
session (``user_tokens`` fed through the decode step, then
``output_tokens`` greedy tokens). After a turn the conversation ends
with probability 1 / ``turns_per_conversation.mean`` (a geometric
number of turns); the session's next turn then starts a new
conversation, and so does a turn that would pass ``context_cap_tokens``.
A session waits between turns with the K/V of its conversation so far;
at set-up each holds a geometric number (at least one) of earlier turns,
cut at ``history.hi`` tokens.

Every size, every conversation end and which idle session each client
picks come from the mix's own ``size_table.seed``, in one fixed order,
so every run serves the same work; ``--seed`` draws the weights and
every token id.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import numpy as np


def _lognormal(spec: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` integer sizes, lognormal with the spec's ``mean`` and
    ``sigma``, rounded and clipped to ``lo``..``hi``."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"size dist {spec['dist']!r} is not lognormal")
    sigma = float(spec["sigma"])
    x = rng.lognormal(np.log(float(spec["mean"])) - sigma**2 / 2, sigma, n)
    return np.clip(np.rint(x), int(spec["lo"]), int(spec["hi"])).astype(np.int64)


@dataclasses.dataclass
class SessionTraffic:
    """The traffic of one run.

    ``histories`` holds each session's history length at set-up;
    ``request`` hands out the next turn's sizes in the table's order.
    """
    mix: Dict
    seed: int
    vocab: int

    def __post_init__(self) -> None:
        m = self.mix
        if m.get("generator") != "closed_loop_sessions":
            raise ValueError(f"unknown generator {m.get('generator')!r}")
        self.clients = int(m["clients"])
        self.sessions = int(m["sessions"])
        self.cap = int(m["context_cap_tokens"])
        if self.clients > self.sessions:
            raise ValueError("more clients than sessions")
        turn_max = int(m["user_tokens"]["hi"]) + int(m["output_tokens"]["hi"]) - 1
        if turn_max > self.cap or int(m["history"]["hi"]) > self.cap:
            raise ValueError("a turn or a history can exceed the context cap")
        turns = m["turns_per_conversation"]
        if turns["dist"] != "geometric":
            raise ValueError(f"turn count dist {turns['dist']!r} is not geometric")
        p_end = 1.0 / float(turns["mean"])
        table = m["size_table"]
        fixed = np.random.default_rng(int(table["seed"]))
        n = int(table["requests"])
        self._user = _lognormal(m["user_tokens"], n, fixed)
        self._out = _lognormal(m["output_tokens"], n, fixed)
        self._ends = fixed.random(n) < p_end
        earlier = fixed.geometric(p_end, self.sessions)
        turn = (_lognormal(m["user_tokens"], int(earlier.sum()), fixed)
                + _lognormal(m["output_tokens"], int(earlier.sum()), fixed))
        hist = np.add.reduceat(turn, np.concatenate([[0], np.cumsum(earlier)[:-1]]))
        self.histories = np.minimum(hist, int(m["history"]["hi"]))
        self._picks = np.random.default_rng([int(table["seed"]), 1])
        self.rng = np.random.default_rng(self.seed)
        self._next = 0

    def request(self) -> Tuple[int, int, bool]:
        """(user tokens, output tokens, whether the conversation ends
        after this turn) of the next turn."""
        i = self._next % len(self._user)
        self._next += 1
        return int(self._user[i]), int(self._out[i]), bool(self._ends[i])

    def pick_session(self, idle: np.ndarray) -> int:
        """A session drawn uniformly from the ``idle`` ones."""
        return int(idle[self._picks.integers(len(idle))])

    def tokens(self, n: int) -> np.ndarray:
        """``n`` token ids, uniform over the vocabulary."""
        return self.rng.integers(0, self.vocab, n, dtype=np.int32)

    def history_tokens(self) -> np.ndarray:
        """(sessions, longest history) token ids of the seeded histories."""
        return self.rng.integers(0, self.vocab,
                                 (self.sessions, int(self.histories.max())),
                                 dtype=np.int32)
