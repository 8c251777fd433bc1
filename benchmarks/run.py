"""Benchmark driver — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--skip-roofline] [--only a,b,...]

``--only`` runs just the named figures (e.g. ``--only replication,batching``
— what the CI benchmark-smoke step uses).  Prints ``name,us_per_call,derived``
CSV rows and tees full results to artifacts/bench_results.json.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, "src")

#: every figure name `--only` may select — kept in sync with the want()
#: sections below so a typo fails loudly instead of silently running nothing
FIGURES = ("latency", "throughput", "cpu_cost", "cleaning", "cluster",
           "batching", "replication", "quorum", "serving_load", "serving_slo",
           "read_speculation", "resharding", "ycsb_driver", "nvm_writes",
           "kernels", "checkpoint", "roofline")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-roofline", action="store_true")
    ap.add_argument("--only", default="",
                    help="comma-separated figure names to run (default: all)")
    args, _ = ap.parse_known_args()
    only = {s.strip() for s in args.only.split(",") if s.strip()}
    unknown = only - set(FIGURES)
    if unknown:
        print(f"unknown figure name(s): {', '.join(sorted(unknown))}\n"
              f"valid figures: {', '.join(FIGURES)}", file=sys.stderr)
        sys.exit(2)

    def want(name: str) -> bool:
        return not only or name in only

    from benchmarks.figures import (bench_cleaning, bench_cpu_cost,
                                    bench_latency, bench_nvm_writes,
                                    bench_throughput)
    from benchmarks.kernels_bench import bench_kernels

    all_rows = []
    print("name,us_per_call,derived")

    if want("latency"):
        rows = bench_latency()
        all_rows += rows
        for r in rows:
            print(f"latency/{r['workload']}/{r['scheme']},{r['avg_us']},"
                  f"v16={r['v16']}us v4096={r['v4096']}us")

    if want("throughput"):
        rows = bench_throughput()
        all_rows += rows
        for r in rows:
            us = 1e3 / r["avg_kops"] if r["avg_kops"] else float("nan")
            print(f"throughput/{r['workload']}/{r['scheme']},{us:.2f},"
                  f"avg={r['avg_kops']}KOp/s t16={r['t16']}KOp/s")

    if want("cpu_cost"):
        rows = bench_cpu_cost()
        all_rows += rows
        for r in rows:
            print(f"cpu_cost/v{r['value_size']}/{r['workload']},,"
                  f"redo={r['redo']}x raw={r['raw']}x")

    if want("cleaning"):
        rows = bench_cleaning()
        all_rows += rows
        for r in rows:
            print(f"cleaning/{r['workload']},{r['during_cleaning_us']},"
                  f"normal={r['normal_us']}us")

    if want("cluster"):
        from benchmarks.figures import bench_cluster_scaling
        rows = bench_cluster_scaling()
        all_rows += rows
        for r in rows:
            us = 1e3 / r["avg_kops"] if r["avg_kops"] else float("nan")
            print(f"cluster/{r['workload']}/shards{r['n_shards']},{us:.2f},"
                  f"avg={r['avg_kops']}KOp/s t64={r['t64']}KOp/s")

    if want("batching"):
        from benchmarks.figures import bench_batching
        rows = bench_batching()
        all_rows += rows
        for r in rows:
            print(f"batching/{r['scheme']}/{r['op']},{r['b8']},"
                  f"seq={r['seq_us']}us b1={r['b1']}us b16={r['b16']}us "
                  f"ratio_b8={r['amortized_ratio_b8']}")

    if want("replication"):
        from benchmarks.figures import bench_replication
        rows = bench_replication()
        all_rows += rows
        for r in rows:
            print(f"replication/v{r['value_size']}/{r['op']},{r['repl_b8']},"
                  f"unrepl_b8={r['unrepl_b8']}us ratio_b1={r['ratio_b1']} "
                  f"ratio_b8={r['ratio_b8']}")

    if want("quorum"):
        from benchmarks.figures import bench_quorum
        rows = bench_quorum()
        all_rows += rows
        for r in rows:
            if r["op"] == "write":
                print(f"quorum/v{r['value_size']}/write,{r['r3_acked_b8']},"
                      f"unrepl_b8={r['unrepl_b8']}us "
                      f"r2_b8={r['r2_acked_b8']}us "
                      f"r3_durable_b8={r['r3_durable_b8']}us "
                      f"ratio_b1={r['r3_ratio_b1']} "
                      f"ratio_b8={r['r3_ratio_b8']}")
            elif r["op"] == "degraded_read":
                print(f"quorum/v{r['value_size']}/degraded_read,"
                      f"{r['degraded_us']},healthy={r['healthy_us']}us "
                      f"ratio={r['ratio']}")
            else:
                print(f"quorum/chaos/{r['op']},,"
                      f"faults={r['faults']} failovers={r['failovers']} "
                      f"epoch_bumps={r['epoch_bumps']} "
                      f"degraded_reads={r['degraded_reads']} "
                      f"stale_rejected={r['stale_rejected']} "
                      f"lost_acked_writes={r['lost_acked_writes']} "
                      f"stale_reads={r['stale_reads']}")

    if want("serving_load"):
        from benchmarks.figures import SERVING_LOADS, bench_serving_load
        rows = bench_serving_load()
        all_rows += rows
        top = SERVING_LOADS[-1]
        for r in rows:
            if r.get("check") == "functional":
                print(f"serving_load/functional,,"
                      f"dispatches={r['dispatches']} "
                      f"stale_or_lost={r['stale_or_lost']} "
                      f"coalesced_equals_sequential="
                      f"{r['coalesced_equals_sequential']}")
                continue
            mode = "coalesce" if r["coalesce"] else "per-op"
            print(f"serving_load/{r['scheme']}/n{r['n_clients']}/{mode},"
                  f"{r['p99_hi_us']},"
                  f"sat={r['saturation_kops']}KOp/s knee={r['knee_kops']} "
                  f"p50_lo={r['p50_lo_us']}us p99_lo={r['p99_lo_us']}us "
                  f"p50_hi={r['p50_hi_us']}us p99_hi={r['p99_hi_us']}us "
                  f"drop_hi={r['drop_rate_hi']} batch_hi={r['mean_batch_hi']} "
                  f"qp_depth={r['qp_max_depth_hi']} "
                  f"hol_ms={r['hol_wait_ms_hi']} "
                  f"kops@{top}={r[f'kops@{top}']}")

    if want("serving_slo"):
        from benchmarks.figures import (SLO_LOADS, YCSB_CONTENDED_THREADS,
                                        bench_serving_slo)
        rows = bench_serving_slo()
        all_rows += rows
        top = SLO_LOADS[-1]
        t_max = YCSB_CONTENDED_THREADS[-1]
        for r in rows:
            check = r.get("check")
            if check == "sharedqp_speedup":
                print(f"serving_slo/sharedqp_speedup,,"
                      f"per_client={r['per_client_sat_kops']}KOp/s "
                      f"shared_qp={r['shared_qp_sat_kops']}KOp/s "
                      f"speedup={r['speedup']}")
            elif check == "slo_goodput":
                print(f"serving_slo/slo_goodput@{r['load_kops']},,"
                      f"slo={r['slo_us']}us "
                      f"queue_goodput={r['queue_goodput_kops']}KOp/s "
                      f"slo_goodput={r['slo_goodput_kops']}KOp/s "
                      f"slo_thr={r['slo_thr_kops']}KOp/s "
                      f"shed={r['slo_shed']} late={r['slo_late']} "
                      f"p99={r['slo_p99_us']}us")
            elif check == "functional":
                print(f"serving_slo/functional,,"
                      f"dispatches={r['dispatches']} "
                      f"stale_or_lost={r['stale_or_lost']} "
                      f"ordering_violations={r['ordering_violations']} "
                      f"coalesced_equals_sequential="
                      f"{r['coalesced_equals_sequential']}")
            elif check == "ycsb_contended":
                print(f"serving_slo/ycsb_contended/{r['workload']},,"
                      f"t1={r['kops@t1']}KOp/s "
                      f"t{t_max}={r[f'kops@t{t_max}']}KOp/s "
                      f"speedup={r['speedup_tmax']}x "
                      f"saturating={r['saturating']}")
            else:
                print(f"serving_slo/{r['mode']},,"
                      f"sat={r['saturation_kops']}KOp/s "
                      f"kops@{top}={r[f'kops@{top}']} "
                      f"batch_hi={r['mean_batch_hi']} "
                      f"batch_p95={r['batch_p95_hi']} "
                      f"head_wait_p99={r['head_wait_p99_us_hi']}us "
                      f"nic_util={r['nic_util_hi']}")

    if want("read_speculation"):
        from benchmarks.figures import bench_read_speculation
        rows = bench_read_speculation()
        all_rows += rows
        for r in rows:
            if "warm_us" in r:
                print(f"read_speculation/v{r['value_size']},{r['warm_us']},"
                      f"cold={r['cold_us']}us miss={r['miss_us']}us "
                      f"warm_cold_ratio={r['warm_cold_ratio']} "
                      f"breakeven={r['breakeven_hit_rate']}")
            else:
                print(f"read_speculation/{r['workload']},{r['spec_us']},"
                      f"spec={r['spec_kops']}KOp/s "
                      f"nospec={r['nospec_kops']}KOp/s "
                      f"speedup={r['speedup']} hit_rate={r['hit_rate']}")

    if want("ycsb_driver"):
        from repro.core import ServerConfig, make_store
        from repro.workloads.ycsb import run_store_workload
        rows = []
        for scheme, kw in (("erda", {}), ("erda-cluster", {"n_shards": 4})):
            cfg = ServerConfig(device_size=64 << 20, table_capacity=1 << 13,
                               n_heads=2, region_size=2 << 20, segment_size=64 << 10)
            r = run_store_workload(make_store(scheme, cfg=cfg, **kw), "ycsb_b",
                                   n_ops=4000, n_keys=400, value_size=256)
            r["figure"] = "ycsb_driver"
            r["scheme"] = scheme
            rows.append(r)
            print(f"ycsb_driver/{r['workload']}/{scheme},,"
                  f"reads={r['reads']} writes={r['writes']} "
                  f"one_sided_reads={r['store_stats'].get('one_sided_reads')} "
                  f"spec_hits={r['spec_hits']} spec_misses={r['spec_misses']} "
                  f"spec_invalidations={r['spec_invalidations']}")
        all_rows += rows

    if want("resharding"):
        from benchmarks.figures import bench_resharding
        rows = bench_resharding()
        all_rows += rows
        for r in rows:
            if r["check"] == "calibration":
                print(f"resharding/calibration,{r['erda_read_us']},"
                      f"raw_read={r['raw_read_us']}us")
            elif r["check"] == "bytes_moved":
                print(f"resharding/bytes_moved/{r['op']},,"
                      f"moved_fraction={r['moved_fraction']} "
                      f"bytes={r['bytes_moved']} "
                      f"minimal={r['minimal_bytes']} ratio={r['ratio']} "
                      f"cutovers={r['cutovers']}")
            elif r["check"] == "elastic_ycsb":
                print(f"resharding/elastic_ycsb,,"
                      f"shards={'->'.join(map(str, r['shards_path']))} "
                      f"lost={r['lost_acked_writes']} "
                      f"stale={r['stale_reads']} "
                      f"straggler_rejections={r['straggler_rejections']} "
                      f"dual_reads={r['dual_reads']} "
                      f"max_ratio={r['max_ratio']}")
            elif r["check"] == "serving_dip":
                print(f"resharding/serving_dip,,"
                      f"base={r['base_kops']}KOp/s "
                      f"during={r['during_kops']}KOp/s "
                      f"after={r['after_kops']}KOp/s "
                      f"dip_ratio={r['dip_ratio']} "
                      f"chains={r['migration_chains']}")

    if want("nvm_writes"):
        rows = bench_nvm_writes()
        all_rows += rows
        for r in rows:
            if "create" in r:
                print(f"nvm_writes/v{r['value_size']}/{r['scheme']},,"
                      f"create={r['create']}B update={r['update']}B delete={r['delete']}B")

    if want("kernels"):
        rows = bench_kernels()
        all_rows += rows
        for r in rows:
            print(f"kernel/{r['name'].replace(' ', '_')},{r['pallas_us']},"
                  f"ref={r['ref_us']}us")

    if want("checkpoint"):
        from benchmarks.checkpoint_bench import bench_checkpoint
        rows = bench_checkpoint()
        all_rows += rows
        for r in rows:
            print(f"checkpoint/{r['name'].replace(' ', '_')},,"
                  f"erda_wamp={r['write_amplification_erda']} "
                  f"redo_wamp={r['write_amplification_redo']} ratio={r['ratio']}")

    if not args.skip_roofline and want("roofline"):
        from benchmarks.roofline_report import summarize
        rows = summarize()  # empty until the dry-run sweep has written reports
        all_rows += rows
        for r in rows[:80]:
            extra = (f"frac={r['roofline_frac']}" if "roofline_frac" in r
                     else r.get("note", ""))
            print(f"roofline/{r['cell']},,dominant={r['dominant']} {extra}")

    out = pathlib.Path("artifacts")
    out.mkdir(exist_ok=True)
    (out / "bench_results.json").write_text(json.dumps(all_rows, indent=1,
                                                       default=str))
    print(f"# wrote {len(all_rows)} rows to artifacts/bench_results.json")


if __name__ == "__main__":
    main()
