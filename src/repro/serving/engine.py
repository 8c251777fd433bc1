"""Batched serving engine: prefill a batch of requests, decode greedily, and
checkpoint decode state into the Erda page store so a preempted replica
resumes bit-identically (the serving-side use of the paper's protocol).

Also the front door for serving the page store AT LOAD: ``serve_kv_at_load``
drives KV page fetches through the open-loop Poisson driver
(``repro.serving.load``) over the contention-aware DES — offered load in,
throughput + tail latency out.  jax is imported lazily (only when a
``ServeEngine`` is built), so the at-load path stays jax-free.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

from repro import obs


class ServeEngine:
    def __init__(self, model, params, *, page_store=None,
                 snapshot_every: int = 0):
        import jax
        from repro.serving.kv_store import ErdaKVPageStore
        self.model = model
        self.params = params
        self.pages = page_store or ErdaKVPageStore()
        self.snapshot_every = snapshot_every
        self._prefill = jax.jit(model.prefill)
        self._decode = jax.jit(model.decode_step)

    def generate(self, batch: Dict, n_tokens: int, *, seq_id: int = 0,
                 crash_at: Optional[int] = None) -> np.ndarray:
        """Greedy decode; optionally 'crash' after `crash_at` tokens (state is
        then restored from the Erda page store and decoding continues)."""
        import jax.numpy as jnp
        with obs.span("serve.generate", seq_id=seq_id):
            with obs.span("serve.prefill", seq_id=seq_id):
                logits, cache = self._prefill(self.params, batch)
            token, host = self._pick(logits, seq_id)
            out = [host]
            step = 0
            while len(out) < n_tokens:
                if self.snapshot_every and step % self.snapshot_every == 0:
                    with obs.span("serve.snapshot", seq_id=seq_id):
                        self.pages.snapshot_cache(seq_id, cache)
                        self.pages.put_page(seq_id, "__tokens__", 0,
                                            np.concatenate(out, axis=1))
                if crash_at is not None and step == crash_at:
                    with obs.span("serve.recover", seq_id=seq_id):
                        cache = self._recover(seq_id, cache)
                        toks = self.pages.get_page(seq_id, "__tokens__", 0)
                    out = [toks[:, i : i + 1] for i in range(toks.shape[1])]
                    crash_at = None
                    token = jnp.asarray(out[-1])
                    continue
                with obs.span("serve.step", seq_id=seq_id):
                    logits, cache = self._decode(self.params, cache, token)
                token, host = self._pick(logits, seq_id)
                out.append(host)
                step += 1
            return np.concatenate(out, axis=1)

    @staticmethod
    def _pick(logits, seq_id: int):
        """The greedy token on the device, and its copy on the host: the
        copy waits for the device to finish the step that made it."""
        import jax.numpy as jnp
        with obs.span("serve.pick", seq_id=seq_id):
            token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        with obs.span("serve.sync", seq_id=seq_id):
            return token, np.asarray(token)

    def _recover(self, seq_id: int, template):
        restored = self.pages.restore_cache(seq_id, template)
        if restored is None:
            raise RuntimeError("no snapshot to recover from")
        return restored


# --------------------------------------------------------- serving at load
#: captured page-fetch trace tables, keyed by geometry (capture is ~100 ms;
#: a load sweep calls serve_kv_at_load once per point)
_page_traces: Dict[Tuple, dict] = {}


def serve_kv_at_load(offered_kops: float, *, n_clients: int = 4,
                     n_shards: int = 2, vsize: int = 1024,
                     read_frac: float = 0.9, coalesce: bool = True,
                     share_qp: bool = False, slo_us: Optional[float] = None,
                     admission: str = "queue", horizon_s: float = 0.02,
                     seed: int = 0, p=None, replication: int = 1,
                     capture_batches: Optional[Tuple[int, ...]] = None,
                     **cfg_kwargs) -> dict:
    """Serve Erda-backed KV page fetches at a fixed OFFERED load (KOp/s).

    Captures doorbell traces of real ``ErdaCluster`` ``multi_read`` /
    ``multi_write`` page ops (once per geometry), then replays Poisson
    arrivals through the contended fabric with bounded admission queues and
    (optionally) adaptive doorbell coalescing.  Returns the
    ``run_open_loop`` report: throughput, p50/p95/p99 per op type, drops,
    per-QP HoL stats, port utilization, persistence lag.

    ``share_qp=True`` merges doorbells ACROSS the client streams sharing
    each (host, shard) QP instead of per client; ``slo_us`` gives every
    request a deadline and turns on goodput accounting, and
    ``admission="slo"`` sheds by earliest infeasible deadline instead of
    queue position (see ``repro.serving.load``).

    ``replication>1`` serves off a quorum-mirrored page store: every write's
    mirror legs ride extra lanes pinned to the host ports that hold the
    backup replicas, so replicated write amplification shows up in NIC
    utilization and write tail latency — and under ``share_qp=True`` the
    mirror lanes coalesce on the same shared QPs as the primary traffic.
    """
    import dataclasses
    from repro.netsim.pricing import SimParams
    from repro.serving.load import (OpenLoopConfig, capture_page_fetch_traces,
                                    run_open_loop)
    p = p or SimParams()
    key = (n_shards, vsize, replication, capture_batches) \
        + dataclasses.astuple(p)
    traces = _page_traces.get(key)
    if traces is None:
        kwargs = {} if capture_batches is None \
            else {"batches": capture_batches}
        traces = _page_traces[key] = capture_page_fetch_traces(
            n_shards=n_shards, vsize=vsize, p=p, replication=replication,
            **kwargs)
    cfg = OpenLoopConfig(offered_kops=offered_kops, n_clients=n_clients,
                         horizon_s=horizon_s, coalesce=coalesce,
                         share_qp=share_qp,
                         slo_s=None if slo_us is None else slo_us * 1e-6,
                         admission=admission,
                         read_frac=read_frac, seed=seed, **cfg_kwargs)
    return run_open_loop(traces, cfg, p)
