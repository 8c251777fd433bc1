import os
# compiles against 512 virtual CPU devices: never open an attached TPU
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS_EXTRA", ""))

"""Multi-pod dry-run: lower + compile every (arch × shape) cell against the
production meshes with ShapeDtypeStruct inputs (no allocation), print
memory/cost analysis, and emit the roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --arch olmo_1b --shape train_4k \
        --mesh single --out artifacts/dryrun

The CPU pin and the 512-device env var above MUST precede any other import (jax locks the
device count at first backend init) — hence the unusual import order.
"""
import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import pathlib           # noqa: E402
import time              # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import SHAPES, cell_applicable, get_config            # noqa: E402
from repro.launch.mesh import make_production_mesh                       # noqa: E402
from repro.models import get_model                                       # noqa: E402
from repro.optim import AdamWConfig                                      # noqa: E402
from repro.roofline.analysis import (model_flops_for, peaks_for,       # noqa: E402
                                     roofline_terms)
from repro.sharding import MeshInfo, batch_spec, cache_specs, param_specs  # noqa: E402
from repro.train import make_train_state_abstract, make_train_step       # noqa: E402


# the chip the production meshes are made of (roofline peaks)
DEVICE_KIND = "TPU v5 lite"

# gradient-accumulation policy for cells whose single-shot activations are too
# tight at 16 GB/chip (memory figures on the CPU backend are ~2× inflated by
# its bf16→f32 dot-operand upcast; see EXPERIMENTS.md §Dry-run)
MICROBATCH_POLICY = {
    ("mixtral_8x22b", "train_4k"): 4,
}

# depth points for the trip-count fit: XLA cost_analysis counts a scan body
# ONCE, so flops/bytes/collective bytes are fitted linearly over model depth
# and extrapolated to the full layer count.
def depth_points(cfg):
    if cfg.family == "encdec":
        return ({"n_layers": 1, "encoder_layers": 1}, 1), \
               ({"n_layers": 2, "encoder_layers": 2}, 2), cfg.n_layers
    if cfg.family == "hybrid":  # through the first, then the second call
        a, b = (i + 1 for i in cfg.hybrid_layer_ids[:2])
        return ({"n_layers": a}, a), ({"n_layers": b}, b), cfg.n_layers
    if cfg.attn_pattern == "local_global":
        g = cfg.local_per_global + 1
        return ({"n_layers": g}, g), ({"n_layers": 2 * g}, 2 * g), cfg.n_layers
    return ({"n_layers": 2}, 2), ({"n_layers": 4}, 4), cfg.n_layers


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               overrides: dict | None = None, policy: str = "tp"):
    cfg = get_config(arch)
    micro_override = None
    if overrides:
        overrides = dict(overrides)
        micro_override = overrides.pop("microbatches", None)
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    model = get_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    info = MeshInfo(mesh)

    specs = model.input_specs(shape)

    # activation batch-sharding constraints (no-op when batch can't shard,
    # e.g. long_500k's B=1 — sequence parallelism covers that case instead)
    from repro.sharding.rules import (batch_axes, set_activation_batch_axes,
                                      set_activation_seq_axis, set_policy)
    set_policy(policy)
    dsz = info.data_size * (info.model_size if policy == "dp" else 1)
    if shape.global_batch % dsz == 0:
        set_activation_batch_axes(batch_axes(info))
    elif shape.global_batch % info.data_size == 0:
        set_activation_batch_axes(info.data_axes)
    else:
        set_activation_batch_axes(None)
    if shape.kind in ("train", "prefill") and policy == "tp" and cfg.seq_parallel:
        set_activation_seq_axis("model", info.model_size)
    else:
        set_activation_seq_axis(None)

    def named(tree):
        return jax.tree.map(
            lambda s: jax.sharding.NamedSharding(mesh, s), tree,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))

    with mesh:
        if shape.kind == "train":
            state = make_train_state_abstract(model, max_seq=shape.seq_len)
            pspec = param_specs(state["params"], info, cfg.n_experts)
            state_spec = {"params": pspec,
                          "opt": {"m": pspec, "v": pspec,
                                  "step": jax.sharding.PartitionSpec()}}
            bspec = batch_spec(specs, info)
            micro = (micro_override if micro_override is not None
                     else MICROBATCH_POLICY.get((arch, shape_name), 1))
            step = make_train_step(model, AdamWConfig(), n_microbatches=micro,
                                   unroll_micro=cfg.unroll)
            jitted = jax.jit(step, in_shardings=(named(state_spec), named(bspec)),
                             donate_argnums=(0,))
            lowered = jitted.lower(state, specs)
        elif shape.kind == "prefill":
            params = model.init_abstract(max_seq=shape.seq_len)
            pspec = param_specs(params, info, cfg.n_experts)
            bspec = batch_spec(specs, info)
            jitted = jax.jit(model.prefill, in_shardings=(named(pspec), named(bspec)))
            lowered = jitted.lower(params, specs)
        else:  # decode
            params = model.init_abstract(max_seq=shape.seq_len)
            pspec = param_specs(params, info, cfg.n_experts)
            cspec = cache_specs(specs["cache"], info, batch_size=shape.global_batch)
            tok_spec = batch_spec({"token": specs["token"]}, info)["token"]
            jitted = jax.jit(model.decode_step,
                             in_shardings=(named(pspec), named(cspec), named(tok_spec)),
                             donate_argnums=(1,))
            lowered = jitted.lower(params, specs["cache"], specs["token"])
    return cfg, shape, mesh, lowered


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str | None,
             overrides: dict | None = None, policy: str = "tp") -> dict:
    multi_pod = mesh_kind == "multi"
    t0 = time.time()
    cfg, shape, mesh, lowered = lower_cell(arch, shape_name,
                                           multi_pod=multi_pod,
                                           overrides=overrides, policy=policy)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    hlo = compiled.as_text()
    chips = mesh.devices.size
    report = roofline_terms(arch=arch, shape=shape_name, mesh_name=mesh_kind,
                            chips=chips, cost=cost, hlo_text=hlo,
                            model_flops=model_flops_for(cfg, shape),
                            device_kind=DEVICE_KIND)
    rec = report.to_json()
    rec.update(
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        bytes_per_device={
            "argument": getattr(mem, "argument_size_in_bytes", None),
            "output": getattr(mem, "output_size_in_bytes", None),
            "temp": getattr(mem, "temp_size_in_bytes", None),
            "peak": (getattr(mem, "argument_size_in_bytes", 0) or 0)
                    + (getattr(mem, "temp_size_in_bytes", 0) or 0),
        },
        ok=True,
    )
    print(f"[dryrun] {arch} × {shape_name} × {mesh_kind}: "
          f"compile {t_compile:.1f}s  "
          f"args {rec['bytes_per_device']['argument'] and rec['bytes_per_device']['argument']/2**30:.2f} GiB/dev  "
          f"temp {rec['bytes_per_device']['temp'] and rec['bytes_per_device']['temp']/2**30:.2f} GiB/dev  "
          f"dominant={rec['dominant']}")
    print(f"  memory_analysis: {mem}")
    print(f"  cost_analysis: flops={cost.get('flops', 0):.3e} "
          f"bytes={cost.get('bytes accessed', 0):.3e}")
    if out_dir:
        p = pathlib.Path(out_dir)
        p.mkdir(parents=True, exist_ok=True)
        (p / f"{arch}__{shape_name}__{mesh_kind}.json").write_text(
            json.dumps(rec, indent=1, default=str))
    return rec


def _measure(arch, shape_name, mesh_kind, overrides, policy="tp"):
    """One lower+compile; returns per-device (flops, bytes, coll_bytes, extras)."""
    multi_pod = mesh_kind == "multi"
    t0 = time.time()
    cfg, shape, mesh, lowered = lower_cell(arch, shape_name,
                                           multi_pod=multi_pod,
                                           overrides=overrides, policy=policy)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    from repro.roofline.analysis import collective_bytes_from_hlo
    coll = collective_bytes_from_hlo(compiled.as_text())
    coll.pop("_counts", None)
    mem = compiled.memory_analysis()
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": float(sum(coll.values())),
        "coll_breakdown": coll,
        "mem": {"argument": getattr(mem, "argument_size_in_bytes", 0),
                "output": getattr(mem, "output_size_in_bytes", 0),
                "temp": getattr(mem, "temp_size_in_bytes", 0)},
        "chips": int(mesh.devices.size),
        "compile_s": round(time.time() - t0, 1),
        "cfg": cfg, "shape": shape,
    }


def run_cell_fit(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
                 overrides: dict | None = None, policy: str = "tp",
                 tag: str = "") -> dict:
    """Trip-count-corrected cell measurement: compile two reduced depths + the
    full model; fit flops/bytes/collective-bytes linearly in depth (scan
    bodies are counted once by cost_analysis); memory comes from the full
    compile."""
    base = dict(overrides or {})
    base.pop("unroll", None)
    cfg0 = get_config(arch)
    if base:
        cfg0 = dataclasses.replace(
            cfg0, **{k: v for k, v in base.items() if k != "microbatches"})
    (ov1, u1), (ov2, u2), u_full = depth_points(cfg0)
    # measurement compiles: unrolled so trip counts are visible to
    # cost_analysis; the full compile stays scanned (memory + compile time).
    # attn_chunk is coarsened: causal chunked attention does the same total
    # math at any chunk size (full rectangle + mask), so fewer unrolled chunk
    # bodies compile faster without changing the counted FLOPs.  Banded (SWA)
    # attention keeps its production chunk (its FLOPs DO depend on it).
    meas = {"unroll": True}
    if cfg0.attn_pattern != "swa" and cfg0.attn_pattern != "local_global":
        meas["attn_chunk"] = max(cfg0.attn_chunk, 4096)
    m1 = _measure(arch, shape_name, mesh_kind, {**base, **ov1, **meas}, policy)
    m2 = _measure(arch, shape_name, mesh_kind, {**base, **ov2, **meas}, policy)
    mf = _measure(arch, shape_name, mesh_kind, base or None, policy)

    def fit(k):
        slope = (m2[k] - m1[k]) / (u2 - u1)
        return slope * u_full + (m1[k] - slope * u1)

    cfg, shape = mf["cfg"], mf["shape"]
    report = roofline_terms(
        arch=arch, shape=shape_name, mesh_name=mesh_kind, chips=mf["chips"],
        cost={"flops": fit("flops"), "bytes accessed": fit("bytes")},
        hlo_text="", model_flops=model_flops_for(cfg, shape),
        device_kind=DEVICE_KIND)
    # collective term fitted separately (fitted from the per-depth HLO parses)
    coll_fit = fit("coll")
    report.collective_bytes_per_chip = coll_fit
    report.collective_s = coll_fit / peaks_for(DEVICE_KIND)["link_bw"]
    rec = report.to_json()
    rec.update(
        raw_scan_once={"flops": mf["flops"], "bytes": mf["bytes"], "coll": mf["coll"]},
        coll_breakdown_full=mf["coll_breakdown"],
        fit_points={"u": [u1, u2, u_full],
                    "flops": [m1["flops"], m2["flops"]],
                    "coll": [m1["coll"], m2["coll"]]},
        bytes_per_device=mf["mem"],
        compile_s=mf["compile_s"], ok=True,
        microbatches=MICROBATCH_POLICY.get((arch, shape_name), 1),
    )
    print(f"[dryrun-fit] {arch} × {shape_name} × {mesh_kind}: "
          f"compute {report.compute_s*1e3:.1f}ms  memory {report.memory_s*1e3:.1f}ms  "
          f"collective {report.collective_s*1e3:.1f}ms  dominant={report.dominant}  "
          f"useful={report.useful_fraction:.2f}  temp={mf['mem']['temp']/2**30:.1f}GiB")
    p = pathlib.Path(out_dir)
    p.mkdir(parents=True, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    (p / f"{arch}__{shape_name}__{mesh_kind}{suffix}.json").write_text(
        json.dumps(rec, indent=1, default=str))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--fit", action="store_true",
                    help="trip-count-corrected 3-compile measurement")
    ap.add_argument("--overrides", default=None,
                    help="JSON dict of ModelConfig field overrides (perf experiments)")
    ap.add_argument("--policy", default="tp", choices=["tp", "dp", "serve"],
                    help="sharding policy (perf experiments)")
    ap.add_argument("--tag", default="", help="output filename suffix")
    args = ap.parse_args()
    if not cell_applicable(args.arch, args.shape):
        print(f"[dryrun] SKIP {args.arch} × {args.shape} (see DESIGN.md §5)")
        return
    overrides = json.loads(args.overrides) if args.overrides else None
    if args.fit:
        run_cell_fit(args.arch, args.shape, args.mesh, args.out, overrides,
                     policy=args.policy, tag=args.tag)
    else:
        run_cell(args.arch, args.shape, args.mesh, args.out, overrides,
                 policy=args.policy)


if __name__ == "__main__":
    main()
