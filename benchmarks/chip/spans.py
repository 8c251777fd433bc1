"""Host spans and counters that the harness records around the program's
layers, from the benchmark's own files: the program gets no new option.

``Recorder.wrap`` replaces an attribute of an engine, page store or store
object with a wrapper that records (label, start, end) on the host clock,
and, while a trace is being taken, opens a ``jax.profiler.TraceAnnotation``
named ``bench:<label>`` so the trace reduction can put device gaps under it.

Token deliveries are read from the same hooks.  The engine syncs each token
to the host right after the prefill or decode call that made it and before it
calls anything else, so a token is delivered at the first hook entered after
that call returns (or when ``generate`` returns).
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable, List, Optional, Tuple

import numpy as np


class Session:
    """What the hooks saw of one ``generate`` call."""

    def __init__(self, seq_id: int, prompt_len: int):
        self.seq_id = seq_id
        self.prompt_len = prompt_len
        self.deliveries: List[Tuple[int, float]] = []  # (position, time)
        self.fed: List[Tuple[int, np.ndarray]] = []  # (position, token)
        self.restores: List[Tuple[float, int]] = []  # (time, next position)
        self.start = self.end = 0.0
        self.output: Optional[np.ndarray] = None
        self.error: Optional[str] = None
        self.pages = None        # the page store the session ran against
        self.snapshotted = None  # the cache last handed to a snapshot

    def release(self) -> None:
        """Drop what the read-back check would need: the session is not
        checked."""
        self.pages = self.snapshotted = None


class Recorder:
    def __init__(self):
        self.spans: List[Tuple[str, float, float]] = []
        self.written: List[Tuple[float, int]] = []  # multi_write (start, bytes)
        self.tracing = False
        self.session: Optional[Session] = None
        self._pending: Optional[int] = None  # position of an undelivered token
        self._next_pos = 0
        self._pages = None
        self.decode_calls: List[Tuple[float, int]] = []  # (time, input pos)
        self._prompt_len = 0

    # ------------------------------------------------------------- sessions
    def begin(self, session: Session) -> None:
        self.session, self._pending, self._next_pos = session, None, 0
        session.start = time.perf_counter()
        session.pages = self._pages

    def end(self, output) -> None:
        self._deliver()
        s = self.session
        s.end = time.perf_counter()
        s.output = output
        self.session = None

    def _deliver(self) -> None:
        if self._pending is not None and self.session is not None:
            self.session.deliveries.append((self._pending, time.perf_counter()))
            self._pending = None

    # ---------------------------------------------------------------- hooks
    @contextlib.contextmanager
    def span(self, label: str):
        """Record (label, start, end) around the block; annotate the trace
        with ``bench:<label>`` while one is being taken."""
        ctx = (_annotation(label) if self.tracing
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ctx:
            yield
        self.spans.append((label, t0, time.perf_counter()))

    def wrap(self, obj, attr: str, label: str, *,
             before: Optional[Callable] = None,
             after: Optional[Callable] = None) -> None:
        """Record a span around ``obj.attr``; ``before(args)`` runs first and
        ``after(args, result)`` last, both outside the timed span."""
        inner = getattr(obj, attr)

        def hooked(*args, **kwargs):
            self._deliver()
            if before is not None:
                before(args)
            with self.span(label):
                result = inner(*args, **kwargs)
            if after is not None:
                after(args, result)
            return result

        setattr(obj, attr, hooked)

    def install(self, engine, prompt_len: int) -> None:
        """Hook the engine's model step, its page store and the store under
        that: the layers the per-layer metrics read."""
        self._prompt_len = prompt_len

        def token_made(args, result):
            self._pending = self._next_pos
            self._next_pos += 1

        def fed(args):
            pos = self._next_pos - 1
            self.decode_calls.append((time.perf_counter(), pos))
            if self.session is not None:
                self.session.fed.append((pos, np.asarray(args[2])))

        self.wrap(engine, "_prefill", "prefill", after=token_made)
        self.wrap(engine, "_decode", "decode", before=fed, after=token_made)
        self.install_pages(engine.pages)

    PAGE_HOOKS = ("snapshot_cache", "restore_cache", "put_page", "get_page")
    STORE_HOOKS = ("multi_write", "multi_read")

    def install_pages(self, pages) -> None:
        """Hook a page store and the store under it; the sessions begun
        from now on run against it."""
        self._pages = pages

        def snapshotted(args):
            if self.session is not None:
                self.session.snapshotted = args[1]

        def restored(args, cache):
            if cache is None:
                return
            pos = int(np.asarray(cache["pos"]))
            self._next_pos = pos - self._prompt_len + 1
            if self.session is not None:
                self.session.restores.append((self.spans[-1][1],
                                              self._next_pos))

        def wrote(args, result):
            self.written.append((self.spans[-1][1],
                                 sum(len(v) for _, v in args[0])))

        self.wrap(pages, "snapshot_cache", "snapshot", before=snapshotted)
        self.wrap(pages, "restore_cache", "restore", after=restored)
        self.wrap(pages, "put_page", "put_page")
        self.wrap(pages, "get_page", "get_page")
        self.wrap(pages.store, "multi_write", "multi_write", after=wrote)
        self.wrap(pages.store, "multi_read", "multi_read")

    def uninstall_pages(self, pages) -> None:
        """Take the wrappers off a page store and its store: their own
        methods are found again, and the wrappers' references are gone."""
        for obj, attrs in ((pages, self.PAGE_HOOKS),
                           (pages.store, self.STORE_HOOKS)):
            for attr in attrs:
                obj.__dict__.pop(attr, None)

    # ------------------------------------------------------------ reading
    def total(self, label: str, lo: float = float("-inf"),
              hi: float = float("inf")) -> Tuple[int, float]:
        """(count, seconds) of the spans of ``label`` that began in [lo, hi)."""
        sel = [b - a for n, a, b in self.spans if n == label and lo <= a < hi]
        return len(sel), sum(sel)


def _annotation(label: str):
    import jax
    return jax.profiler.TraceAnnotation("bench:" + label)
