"""Mini dry-run in CI: a (2,2,2) pod×data×model mesh over 8 forced host
devices, scaled-down configs, lower+compile for all three step kinds.  Runs in
a SUBPROCESS because jax locks the device count at first init."""
import json
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # JAX model/train lane; excluded from tier-1

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json, sys
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.models import get_model
    from repro.optim import AdamWConfig
    from repro.sharding import MeshInfo, batch_spec, cache_specs, param_specs
    from repro.sharding.rules import set_activation_batch_axes, set_activation_seq_axis
    from repro.train import make_train_state_abstract, make_train_step

    arch = sys.argv[1]
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    info = MeshInfo(mesh)
    cfg = dataclasses.replace(get_config(arch).scaled_down(), d_model=64,
                              head_dim=16, n_heads=4, n_kv_heads=2 if arch != "whisper_small" else 4)
    model = get_model(cfg)
    results = {}
    with mesh:
        named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                       is_leaf=lambda x: isinstance(x, P))
        # train
        set_activation_batch_axes(info.data_axes)
        set_activation_seq_axis("model", info.model_size)
        shape = ShapeConfig("t", 64, 8, "train")
        specs = model.input_specs(shape)
        state = make_train_state_abstract(model, max_seq=96)
        pspec = param_specs(state["params"], info, cfg.n_experts)
        sspec = {"params": pspec, "opt": {"m": pspec, "v": pspec, "step": P()}}
        step = make_train_step(model, AdamWConfig())
        c = jax.jit(step, in_shardings=(named(sspec), named(batch_spec(specs, info)))
                    ).lower(state, specs).compile()
        results["train"] = c.cost_analysis().get("flops", 0) > 0
        # decode
        set_activation_seq_axis(None)
        shape = ShapeConfig("d", 64, 8, "decode")
        specs = model.input_specs(shape)
        params = model.init_abstract(max_seq=96)
        pspec = param_specs(params, info, cfg.n_experts)
        cspec = cache_specs(specs["cache"], info, batch_size=8)
        tspec = batch_spec({"token": specs["token"]}, info)["token"]
        c = jax.jit(model.decode_step,
                    in_shardings=(named(pspec), named(cspec), named(tspec))
                    ).lower(params, specs["cache"], specs["token"]).compile()
        results["decode"] = True
    print(json.dumps(results))
""")


@pytest.mark.parametrize("arch", ["olmo_1b", "mixtral_8x22b", "rwkv6_1p6b",
                                  "gemma3_12b", "zamba2_1p2b", "zamba2_7b"])
def test_small_mesh_dryrun(arch):
    r = subprocess.run([sys.executable, "-c", SCRIPT, arch],
                       capture_output=True, text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["train"] and out["decode"]
