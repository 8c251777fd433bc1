"""Tiny cells for the CPU tests: the benchmark's own configurations at the
program's smoke widths (``ModelConfig.scaled_down``), short mixes, and a page
store a few MiB large."""
from __future__ import annotations

import copy
import dataclasses
import json
import pathlib

import spec
from traffic import Mix

HERE = pathlib.Path(__file__).resolve().parent.parent
MANIFEST = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())


def tiny_config(name: str) -> dict:
    from repro.configs import get_config
    config = spec.load_json(HERE / "configs" / f"{name}.json")
    small = get_config(config["program_arch"]).scaled_down()
    model = config["model"]
    for k in ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab_size"):
        if k in model:
            model[k] = getattr(small, k)
    if config["family"] == "rwkv6":
        model["head_dim"] = model["d_model"] // model["n_heads"]
    config["page_store"].update(nvm_bytes_per_shard=512 << 20,
                                region_bytes=4 << 20, segment_bytes=1 << 20,
                                table_capacity=4096)
    return config


def tiny_cell(config_name: str, traffic: str, *, new_tokens: int = 12,
              limit: float = 0.05) -> spec.Cell:
    mix = spec.load_json(HERE / "traffic" / f"{traffic}.json")
    preempt = bool(mix["preempt_steps"])
    # as the real mixes: a preempted session snapshots once, at step 0
    mix.update(prompt_len=16, new_tokens=new_tokens,
               snapshot_every=(2 * new_tokens if preempt
                               else min(mix["snapshot_every"], 4)),
               preempt_steps=[2, 5, 9] if preempt else [])
    name = f"{config_name}.{traffic}"
    return spec.Cell(name, 1, tiny_config(config_name), Mix.from_dict(mix),
                     {"logit_gap": {"limit": limit}},
                     spec._for_cell(copy.deepcopy(MANIFEST["end_to_end"]), name),
                     spec._for_cell(copy.deepcopy(MANIFEST["per_layer"]), name))
