#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout that holds the program (``src/``).  The cell
(``BENCHMARK.json``) names a configuration and a traffic mix; everything
else is found by those names under this directory.

Phases:
1. set-up (``setup_s``): JAX must find a TPU with as many chips as the cell
   asks for, or the run fails with no result.  The configuration's model is
   built through the program's registry at the file's widths, its weights
   made on the device from the seed in one jitted call, a page store built
   over an Erda cluster sized by the configuration, and every program the
   window runs compiled (or loaded from the compile cache) by one short
   session against a host-memory stand-in for the page store.
2. window: sessions of the mix run back to back through
   ``ServeEngine.generate``, one replica's closed decode loop, until
   ``--seconds`` have passed; the window closes when the last one ends.
   Where the mix snapshots, each session gets a page store of its own
   (built in well under a millisecond; the span ``new_store`` also holds
   the collection that frees retired stores' memory, every 4 GiB), since
   the program's log never gives space back and one store would fill
   within a few sessions.
   The harness's hooks (``spans.py``) time the model step, the page store
   and the store under it.  With ``--trace 1`` the profiler records the
   first ~10 s of the window (to a session's end) for the device metrics.
3. check (``check.py``): after the window, page-store read-back (against
   the cache each sampled session last handed to a snapshot) and token
   replay; then the program's device state is freed and the plain float32
   reference is run over the same seeded sample of the sessions.

The last stdout line is the result as one JSON object; the numbers compared
for ``correct`` are its last key and the last lines on stderr.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from typing import Dict, List, Optional, Sequence  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT / "src"))

import numpy as np  # noqa: E402

import check  # noqa: E402
import spec  # noqa: E402
from spans import Recorder, Session  # noqa: E402
from traffic import Sessions  # noqa: E402

#: host memory that retired page stores may hold before a collection frees
#: it (a store holds reference cycles; collecting after every session would
#: cost tens of milliseconds each time)
FREE_EVERY_BYTES = 4 << 30

#: seconds of the window the profiler records in a traced run (it stops at
#: the first session end past this); a longer trace is too large to read
#: within a run's time
TRACE_SECONDS = 10.0


class NoChip(RuntimeError):
    pass


# ------------------------------------------------------------------ set-up
def use_compile_cache() -> None:
    """JAX's persistent compile cache at ``JAX_COMPILATION_CACHE_DIR`` if
    set, else at the checkout's fixed ``.jax_cache``; every program is cached,
    however fast it compiled, so a second run compiles nothing."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips(n: int):
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX finds no accelerator: {e}") from None
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU, only {devices[0].platform!r}")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX finds {len(devices)}")
    return devices


def program_model(config: Dict):
    """The program's model at the configuration file's widths.  A key the
    program's configuration has no field for is an error, so no number in
    the file goes unapplied, unless the file lists it under
    ``reference_only``: a width the program fixes in its code, which the
    reference's cost functions read."""
    from repro.configs import get_config
    from repro.models import get_model
    cfg = get_config(config["program_arch"])
    fields = {f.name for f in dataclasses.fields(cfg)}
    skip = set(config.get("reference_only", ()))
    unknown = sorted(set(config["model"]) - fields - skip)
    if unknown:
        raise ValueError(f"the program cannot apply model keys {unknown}")
    return get_model(dataclasses.replace(cfg, **{
        k: v for k, v in config["model"].items() if k not in skip}))


def page_store(config: Dict):
    from repro.core import ServerConfig, make_store
    from repro.serving.kv_store import ErdaKVPageStore
    ps = config["page_store"]
    shard = ServerConfig(device_size=ps["nvm_bytes_per_shard"],
                         table_capacity=ps["table_capacity"],
                         n_heads=ps["n_heads"], region_size=ps["region_bytes"],
                         segment_size=ps["segment_bytes"])
    return ErdaKVPageStore(store=make_store(
        "erda-cluster", n_shards=ps["n_shards"],
        replication=ps["replication"], cfg=shard))


class HostPages:
    """Stand-in page store for the warm-up: snapshots kept as host arrays,
    so the warm-up compiles what the window runs (a restore feeds the decode
    step host arrays) without writing to the Erda store."""

    def __init__(self):
        self.kept = {}

    def snapshot_cache(self, seq_id, cache):
        import jax
        self.kept[seq_id] = jax.tree.map(np.asarray, cache)

    def restore_cache(self, seq_id, template):
        return self.kept.get(seq_id)

    def put_page(self, seq_id, name, idx, array):
        self.kept[(seq_id, name, idx)] = np.asarray(array)

    def get_page(self, seq_id, name, idx):
        return self.kept.get((seq_id, name, idx))


def tally(totals: Dict[str, float], pages) -> int:
    """Add a page store's counters, and the bytes written to its simulated
    NVM, to ``totals``; returns those bytes."""
    written = sum(d.stats.bytes_written for d in pages.store.devs)
    for k, v in {**pages.counters, "nvm_bytes_written": written}.items():
        totals[k] = totals.get(k, 0) + v
    return written


# ------------------------------------------------------------------ metrics
@dataclasses.dataclass
class Context:
    """What a metric reader (``metrics/<name>.py``) may read."""
    cell: spec.Cell
    family: object
    peaks: Dict[str, float]
    setup_s: float
    rec: Recorder
    sessions: List[Session]
    window: tuple                  # (start, end), host clock
    counters: Dict[str, float]     # the program's counters over the window
    trace: Optional[Dict] = None   # devtrace.reduce(...) of the traced part
    trace_window: Optional[tuple] = None  # its (start, end), host clock

    @property
    def layer_window(self):
        """The part of the window the host-span metrics of a layer read: the
        traced part, as the device metrics do.  On the chip's host the page
        store's calls run ~3x faster once a profiler session has ended
        (PERF.md), so the rest of a traced run's window is not like an
        untraced one's."""
        return self.trace_window or self.window

    @property
    def batch(self) -> int:
        return self.cell.mix.batch

    def decode_calls(self, lo: float, hi: float):
        """Context lengths (prompt + positions fed so far) of the decode
        steps called in [lo, hi)."""
        P = self.cell.mix.prompt_len
        return [P + pos + 1 for t, pos in self.rec.decode_calls
                if lo <= t < hi]


def read_metrics(ctx: Context, entries: List[Dict]) -> Dict[str, Dict]:
    out = {}
    for e in entries:
        value = spec.metric_reader(e["name"])(ctx)
        if value is not None:
            out[e["name"]] = {"value": value, "unit": e["unit"]}
    return out


# ------------------------------------------------------------------ the run
def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             need_tpu: bool = True, controls: Sequence[str] = ()) -> Dict:
    """One run; returns the result object.  ``controls`` (for calibration)
    also reads the widest gap of the reference run in each named lower
    precision standing in for the program, under ``result["controls"]``."""
    import jax
    import jax.numpy as jnp
    from peaks import peaks_for
    from weights import make_init, seed_key

    marks = [("start", T_START), ("imports", time.perf_counter())]
    devices = chips(cell.chips) if need_tpu else jax.devices()
    marks.append(("devices", time.perf_counter()))
    peaks = peaks_for(devices[0].device_kind) if need_tpu else {}
    mix, config = cell.mix, cell.config
    fam = spec.family(config)
    model = program_model(config)
    from repro.serving import ServeEngine

    shapes = model.init_abstract(mix.prompt_len + mix.new_tokens)
    init = make_init(shapes, config["init"])
    params = jax.block_until_ready(init(seed_key(seed)))
    marks.append(("weights", time.perf_counter()))
    pages = page_store(config)
    marks.append(("page store", time.perf_counter()))
    engine = ServeEngine(model, params, page_store=pages,
                         snapshot_every=mix.snapshot_every)
    sessions = Sessions(mix, config["model"]["vocab_size"], seed)
    warm = sessions.warmup()
    engine.pages = HostPages()
    jax.block_until_ready(engine.generate(
        {"tokens": jnp.asarray(warm.tokens)}, min(mix.new_tokens, 4),
        seq_id=warm.seq_id, crash_at=warm.crash_at))
    engine.pages = pages
    template = jax.eval_shape(model.prefill, params,
                              {"tokens": jnp.asarray(warm.tokens)})[1]
    rec = Recorder()
    rec.install(engine, mix.prompt_len)
    lowered = []  # programs lowered (a jit cache miss) from here on

    def on_lowering(event, secs, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(time.perf_counter())
    jax.monitoring.register_event_duration_secs_listener(on_lowering)
    setup_s = time.perf_counter() - T_START
    marks.append(("warm-up", T_START + setup_s))
    print("[bench] set-up s: " + ", ".join(
        f"{b[0]} {b[1] - a[1]:.3f}" for a, b in zip(marks, marks[1:])),
        file=sys.stderr, flush=True)

    # ------------------------------------------------------------- window
    counters: Dict[str, float] = {}  # every store of the window starts at 0
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    window_note, trace_window = None, None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        rec.tracing = True
        window_note = jax.profiler.TraceAnnotation("bench:window")
        window_note.__enter__()
    done: List[Session] = []
    picked = check.Sample(mix.check_sessions, seed)
    unfreed = 0  # NVM bytes of retired stores not yet collected
    t0 = time.perf_counter()
    i = 0
    while True:
        req = sessions[i]
        if mix.snapshot_every and i:
            with rec.span("new_store"):
                unfreed += tally(counters, engine.pages)
                rec.uninstall_pages(engine.pages)
                engine.pages = page_store(config)
                rec.install_pages(engine.pages)
                if unfreed >= FREE_EVERY_BYTES:
                    gc.collect()  # stores hold reference cycles
                    unfreed = 0
        s = Session(req.seq_id, mix.prompt_len)
        rec.begin(s)
        try:
            out = engine.generate({"tokens": jnp.asarray(req.tokens)},
                                  mix.new_tokens, seq_id=req.seq_id,
                                  crash_at=req.crash_at)
        except Exception:  # a failed session ends the window; it is counted
            s.error = traceback.format_exc()
            print(s.error, file=sys.stderr, flush=True)
            rec.end(None)
            s.release()
            done.append(s)
            break
        rec.end(np.asarray(out))
        picked.offer(s)
        done.append(s)
        i += 1
        now = time.perf_counter()
        if window_note is not None and now - t0 >= TRACE_SECONDS:
            window_note.__exit__(None, None, None)
            jax.profiler.stop_trace()
            rec.tracing, window_note, trace_window = False, None, (t0, now)
        if now - t0 >= seconds:
            break
    t1 = time.perf_counter()
    if window_note is not None:
        window_note.__exit__(None, None, None)
        jax.profiler.stop_trace()
        rec.tracing, trace_window = False, (t0, t1)

    # ------------------------------------------------------ after the window
    jax.monitoring.unregister_event_duration_listener(on_lowering)
    print(f"[bench] programs lowered in the window: "
          f"{sum(t0 <= t <= t1 for t in lowered)}", file=sys.stderr)
    for label in sorted({n for n, _, _ in rec.spans}):
        took = [b - a for n, a, b in rec.spans if n == label and t0 <= a < t1]
        if took:
            print(f"[bench] span {label}: n {len(took)} total_s {sum(took)!r}"
                  f" min_s {min(took)!r} max_s {max(took)!r}",
                  file=sys.stderr)
    stats = [d.memory_stats() or {} for d in devices[:cell.chips]]
    memory_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    tally(counters, engine.pages)
    reduced = None
    if trace:
        import devtrace
        files = list(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
        reduced = devtrace.reduce_file(str(files[0]))
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = Context(cell, fam, peaks, setup_s, rec, done, (t0, t1), counters,
                  reduced, trace_window)
    metrics = read_metrics(ctx, cell.per_layer if trace else cell.end_to_end)

    finished = [s for s in done if s.error is None]
    picked = picked.kept
    values = {"sessions_failed": float(len(done) - len(finished)),
              "token_replay_mismatch": float(check.token_replay_mismatch(done))}
    if mix.snapshot_every:
        values["snapshot_readback_bad"] = float(check.snapshot_readback_bad(
            picked, template, mix.prompt_len))
    # free the program's device state before the reference runs
    for s in done:
        s.release()
    del engine, pages, params
    for a in jax.live_arrays():
        a.delete()
    gc.collect()
    ref_params = init(seed_key(seed))
    gaps = check.served_gaps(fam, ref_params, config["model"], picked,
                             [sessions[s.seq_id].tokens for s in picked])
    values["logit_gap"] = float(gaps.max()) if gaps.size else None
    control = {q: float(check.served_gaps(
        fam, ref_params, config["model"], picked,
        [sessions[s.seq_id].tokens for s in picked], quant=q).max())
        for q in controls if picked}
    del ref_params
    limits = {k: 0.0 for k in check.EXACT}
    limits["logit_gap"] = (cell.limits or {}).get("logit_gap", {}).get("limit")

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": check.verdict(values, limits),
              "attempted": len(done) * mix.batch,
              "failed": (len(done) - len(finished)) * mix.batch,
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    if controls:
        result["controls"] = control
    result["checks"] = {k: {"value": v, "limit": limits[k]}
                        for k, v in values.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    if cell.limits is None:
        print(f"[bench] no limits/{cell.name}.json", file=sys.stderr)
        return 1
    use_compile_cache()
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 1
    for k, c in result["checks"].items():
        print(f"[bench] check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
