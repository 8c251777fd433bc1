"""Serving examples.

1. Batched greedy decode with Erda-backed KV snapshots and a simulated
   mid-decode preemption — the continuation is bit-identical.
2. The same page store served AT LOAD: an open-loop Poisson driver fetches
   KV pages through the contention-aware DES at two offered loads — one
   below the saturation knee (tail ~= the uncontended latency) and one past
   it (queueing tail, adaptive doorbell coalescing earning its keep).
3. Shared-QP coalescing + SLO-aware admission: 16 streams merge doorbell
   runs on shared per-(host,shard) QPs, and every request carries a
   deadline (``--slo-us``, default 250).  Below the knee both admission
   policies serve everything in-deadline; at 1.2× past it the queue-bound
   policy's completions are almost all late while deadline shedding keeps
   goodput near saturation.

    PYTHONPATH=src python examples/serve_kv.py [--slo-us 250]
"""
import argparse

import numpy as np

from repro.launch.serve import serve
from repro.serving import serve_kv_at_load

args = argparse.ArgumentParser()
args.add_argument("--slo-us", type=float, default=250.0,
                  help="per-request deadline for the SLO-admission demo (µs)")
args = args.parse_args()

# ------------------------------------------ preemption / recovery (jax side)
clean, _ = serve(arch="rwkv6_1p6b", scale="smoke", batch=2, prompt_len=32,
                 tokens=16, snapshot_every=4)
crashy, stats = serve(arch="rwkv6_1p6b", scale="smoke", batch=2, prompt_len=32,
                      tokens=16, snapshot_every=4, crash_at=9)
np.testing.assert_array_equal(clean, crashy)
assert stats["restores"] == 1
print(f"generated {clean.shape[1]} tokens × {clean.shape[0]} requests")
print("preempted replica restored from the Erda page store: outputs identical")

# ------------------------------------------------ serving at load (DES side)
print("\nopen-loop KV page fetches, 2-shard Erda cluster, 8 clients:")
print(f"{'offered':>10} {'coalesce':>9} {'achieved':>10} {'p50':>9} "
      f"{'p99':>9} {'drops':>6} {'batch':>6}")
for offered_kops in (120.0, 900.0):          # below the knee / past saturation
    for coalesce in (False, True):
        r = serve_kv_at_load(offered_kops, n_clients=8, n_shards=2,
                             horizon_s=0.02, read_frac=0.9, coalesce=coalesce)
        lat = r["latency"]["all"]
        print(f"{offered_kops:8.0f}k {str(coalesce):>9} "
              f"{r['throughput_kops']:8.1f}k {lat['p50_us']:7.1f}us "
              f"{lat['p99_us']:7.1f}us {r['dropped']:6d} "
              f"{r['mean_batch']:6.2f}")
lo = serve_kv_at_load(120.0, n_clients=8, n_shards=2, horizon_s=0.02)
hi = serve_kv_at_load(900.0, n_clients=8, n_shards=2, horizon_s=0.02)
assert hi["latency"]["all"]["p99_us"] > lo["latency"]["all"]["p99_us"]
print("past the knee the p99 queueing tail opens up; coalescing holds "
      "throughput at the offered load the per-op doorbells cannot reach")

# --------------------------- shared-QP coalescing + SLO admission (DES side)
print(f"\nshared-QP coalescing, 16 clients / 4 shards, slo={args.slo_us:.0f}us:")
print(f"{'offered':>10} {'admission':>9} {'achieved':>10} {'goodput':>10} "
      f"{'shed':>6} {'late':>6} {'p99':>9}")
for offered_kops in (400.0, 3840.0):         # below the knee / 1.2x past it
    for admission in ("queue", "slo"):
        r = serve_kv_at_load(offered_kops, n_clients=16, n_shards=4,
                             horizon_s=0.006, read_frac=0.9, seed=3,
                             share_qp=True, b_max=64,
                             capture_batches=(1, 2, 4, 8, 16, 32, 64),
                             slo_us=args.slo_us, admission=admission)
        s = r["slo"]
        print(f"{offered_kops:8.0f}k {admission:>9} "
              f"{r['throughput_kops']:8.1f}k {s['goodput_kops']:8.1f}k "
              f"{s['shed']:6d} {s['late']:6d} "
              f"{r['latency']['all']['p99_us']:7.1f}us")
print("past the knee the queue-bound backlog makes completions late "
      "(throughput without goodput); deadline shedding serves only feasible "
      "requests and keeps goodput near saturation")
