"""The comparison that decides ``correct``.

What the timed path produced is compared, after the window, with what it
must be:

- ``logit_gap``: a sample of the finished sessions, drawn from the seed, is
  run through the family's plain float32 reference (prompt and served tokens,
  teacher-forced); at each served position the gap by which the served
  token's reference logit lies below the reference's best.  The widest gap
  over the sample is compared with the cell's limit (``limits/<cell>.json``).
- ``token_replay_mismatch``: every token the decode loop was fed, before a
  preemption and after it, equals the session's final token at that
  position: a resumed session emits what it had emitted.  Limit 0.
- ``snapshot_readback_bad``: for each sampled session, its last acknowledged
  snapshot reads back from the page store as the cache the engine handed to
  it, every leaf bit for bit, with the session's tokens up to the cache's
  position.  Limit 0.
- ``sessions_failed``: sessions that raised.  Limit 0.

The control (the reference in a lower precision standing in for the
program) reads the same gap for the token it would have served.
"""
from __future__ import annotations

import traceback
from typing import Dict, Optional, Sequence

import numpy as np

EXACT = ("token_replay_mismatch", "snapshot_readback_bad", "sessions_failed")


class Sample:
    """k of the finished sessions, drawn from the seed as they finish
    (reservoir sampling), so that only the sampled sessions keep their page
    store and last snapshotted cache.  Every session of a mix has the same
    length, so the sample holds the longest."""

    def __init__(self, k: int, seed: int):
        self.k, self.seen, self.kept = k, 0, []
        self.rng = np.random.default_rng([seed, 4])

    def offer(self, session) -> None:
        self.seen += 1
        if len(self.kept) < self.k:
            self.kept.append(session)
            return
        j = int(self.rng.integers(self.seen))
        if j < self.k:
            self.kept[j].release()
            self.kept[j] = session
        else:
            session.release()


def token_replay_mismatch(sessions: Sequence) -> int:
    bad = 0
    for s in sessions:
        if s.output is None:
            continue
        for pos, tok in s.fed:
            if not 0 <= pos < s.output.shape[1]:  # fed at no served position
                bad += tok.size
                continue
            bad += int((s.output[:, pos] != tok.reshape(-1)).sum())
    return bad


def same_bits(a, b) -> bool:
    """Two trees with the same structure, shapes, dtypes and bytes."""
    import jax
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    if ta != tb:
        return False
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if (x.dtype, x.shape) != (y.dtype, y.shape) or \
                x.tobytes() != y.tobytes():
            return False
    return True


def snapshot_readback_bad(sessions: Sequence, template,
                          prompt_len: int) -> int:
    """Sessions whose last snapshot does not read back as it must."""
    bad = 0
    for s in sessions:
        try:
            toks = s.pages.get_page(s.seq_id, "__tokens__", 0)
            cache = s.pages.restore_cache(s.seq_id, template)
        except Exception:  # a read the store refuses reads back nothing
            traceback.print_exc()
            bad += 1
            continue
        n = 0 if toks is None else toks.shape[1]
        ok = (toks is not None and cache is not None
              and s.snapshotted is not None
              and same_bits(cache, s.snapshotted)
              and np.array_equal(toks, s.output[:, :n])
              and int(np.asarray(cache["pos"])) == prompt_len + n - 1)
        bad += not ok
    return bad


def served_gaps(fam, params, model: Dict, sessions: Sequence,
                prompts: Sequence[np.ndarray], quant: Optional[str] = None):
    """Per sampled row and served position, the reference's best logit minus
    its logit for the served token; with ``quant`` the served token is the one
    the reference in that precision puts first instead.  Returns a float32
    array (rows, served positions)."""
    import jax
    import jax.numpy as jnp

    if not sessions:
        return np.zeros((0, 0), np.float32)
    P = prompts[0].shape[1]
    served = np.concatenate([s.output for s in sessions], axis=0)
    tokens = np.concatenate([np.concatenate([p, s.output[:, :-1]], axis=1)
                             for p, s in zip(prompts, sessions)], axis=0)
    with jax.default_matmul_precision("highest"):
        ref = fam.logits(params, jnp.asarray(tokens), model)[:, P - 1:]
        if quant is None:
            pick = jnp.asarray(served)
        else:
            low = fam.logits(params, jnp.asarray(tokens), model, quant)
            pick = jnp.argmax(low[:, P - 1:], axis=-1)
            del low
        chosen = jnp.take_along_axis(ref, pick[..., None], axis=-1)[..., 0]
        return np.asarray(ref.max(-1) - chosen)


def verdict(values: Dict[str, Optional[float]],
            limits: Dict[str, Optional[float]]) -> bool:
    """Every number read and within its limit (a missing one fails)."""
    return all(values[k] is not None and limits[k] is not None
               and values[k] <= limits[k] for k in values)
