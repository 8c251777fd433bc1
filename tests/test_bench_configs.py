"""Every configuration file of the on-chip benchmark stays servable by the
program: each ``"model"`` key the program applies is a ``ModelConfig`` field
of its ``program_arch``, and the registry builds the model at those widths.
A key the program lost would fail the cell on the chip."""
import dataclasses
import json
import pathlib

import jax
import pytest

from repro.configs import get_config
from repro.models import get_model

CONFIGS = sorted((pathlib.Path(__file__).resolve().parents[1]
                  / "benchmarks" / "chip" / "configs").glob("*.json"))


def test_the_benchmark_has_configuration_files():
    assert CONFIGS


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_benchmark_config_builds_in_the_program(path):
    config = json.loads(path.read_text())
    cfg = get_config(config["program_arch"])
    fields = {f.name for f in dataclasses.fields(cfg)}
    applied = {k: v for k, v in config["model"].items()
               if k not in config.get("reference_only", ())}
    assert set(applied) <= fields, sorted(set(applied) - fields)
    model = get_model(dataclasses.replace(cfg, **applied))
    params = model.init_abstract(256)
    assert jax.tree.leaves(params)
    assert model.cfg.family == cfg.family
