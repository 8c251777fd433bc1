"""The one traffic generator: sessions for a closed decode loop, from a mix
file and the seed.

A mix file (``traffic/<mix>.json``) holds:
  batch, prompt_len, new_tokens  -- the shape of every session (fixed, since
                                    each new shape compiles)
  snapshot_every                 -- the engine's snapshot period, 0 for none
  preempt_steps                  -- decode steps at which sessions are
                                    preempted, taken in blocks: every block of
                                    len(preempt_steps) sessions uses each once,
                                    in an order drawn from the seed; empty for
                                    no preemption
  check_sessions                 -- finished sessions the correctness check
                                    samples
  why, assumed                   -- what the mix exercises, and which of its
                                    numbers no published trace gave
Prompts are uniform token ids drawn from the seed, different per session.
Every seed gets the same sizes and preempt steps; only their order and the
token values change.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

KEYS = ("arrival", "batch", "prompt_len", "new_tokens", "snapshot_every",
        "preempt_steps", "check_sessions", "why", "assumed")


@dataclasses.dataclass(frozen=True)
class Mix:
    arrival: str
    batch: int
    prompt_len: int
    new_tokens: int
    snapshot_every: int
    preempt_steps: List[int]
    check_sessions: int
    why: str
    assumed: str

    @classmethod
    def from_dict(cls, d: dict) -> "Mix":
        if set(d) != set(KEYS):
            raise ValueError(f"mix keys {sorted(d)} != {sorted(KEYS)}")
        if d["arrival"] != "closed":
            raise ValueError("only closed-loop mixes are generated")
        mix = cls(**d)
        last = mix.new_tokens - 2  # the last decode step that has a successor
        if any(not 1 <= s <= last for s in mix.preempt_steps):
            raise ValueError(f"preempt steps must lie in [1, {last}]")
        return mix


@dataclasses.dataclass(frozen=True)
class Request:
    seq_id: int
    tokens: np.ndarray        # (batch, prompt_len) int32
    crash_at: Optional[int]   # decode step of the preemption, or None


class Sessions:
    """Session i of a run, for any i, drawn from (seed, i)."""

    def __init__(self, mix: Mix, vocab: int, seed: int):
        self.mix, self.vocab, self.seed = mix, vocab, seed

    def __getitem__(self, i: int) -> Request:
        m = self.mix
        rng = np.random.default_rng([self.seed, 1, i])
        tokens = rng.integers(0, self.vocab, (m.batch, m.prompt_len),
                              dtype=np.int32)
        crash = None
        if m.preempt_steps:
            block, at = divmod(i, len(m.preempt_steps))
            order = np.random.default_rng([self.seed, 2, block]).permutation(
                len(m.preempt_steps))
            crash = int(m.preempt_steps[order[at]])
        return Request(i, tokens, crash)

    def warmup(self) -> Request:
        """A request outside the run's sessions (its own stream)."""
        rng = np.random.default_rng([self.seed, 3])
        tokens = rng.integers(0, self.vocab,
                              (self.mix.batch, self.mix.prompt_len),
                              dtype=np.int32)
        return Request(-1, tokens, 1 if self.mix.preempt_steps else None)
