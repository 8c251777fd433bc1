"""Parameter counts, decode FLOPs and bytes from the configuration shapes,
against hand counts and against the program's own parameter tree."""
import math

import jax
import pytest

import run
import spec


def _tree_count(config) -> int:
    shapes = run.program_model(config).init_abstract(256)
    return sum(math.prod(s.shape) for s in jax.tree.leaves(shapes))


def test_olmo_1b_hand_count():
    m = spec.load_json(spec.HERE / "configs" / "olmo_1b.json")["model"]
    fam = spec.family({"family": "dense"})
    # per layer 4 d^2 (q, k, v, o) + 3 d f (SwiGLU); tied 50304 x 2048 table
    per_layer = 4 * 2048 ** 2 + 3 * 2048 * 8192
    hand = 16 * per_layer + 50304 * 2048
    assert fam.param_count(m) == hand == 1_176_764_416
    flops, moved = fam.decode_cost(m, 4, 200)
    assert moved == pytest.approx(2 * hand + 16 * 2 * 4 * 2048 * 2 * 200)
    assert 2.35e9 < 2 * hand < 2.36e9
    attn = 16 * 4 * 2048 * 200
    assert flops == pytest.approx(4 * (2 * (16 * per_layer + 50304 * 2048)
                                       + attn))


@pytest.mark.parametrize("name", ["olmo_1b", "rwkv6_1p6b"])
def test_param_count_matches_program_tree(name):
    config = spec.load_json(spec.HERE / "configs" / f"{name}.json")
    assert spec.family(config).param_count(config["model"]) == \
        _tree_count(config)


def test_rwkv6_state_bytes():
    m = spec.load_json(spec.HERE / "configs" / "rwkv6_1p6b.json")["model"]
    fam = spec.family({"family": "rwkv6"})
    assert fam.state_bytes(m, 4) == 24 * 4 * (32 * 64 * 64 * 4 + 2 * 2048 * 2)
    flops, moved = fam.decode_cost(m, 4, 1)
    assert moved > 2.9e9 and flops > 2 * 4 * 1.4e9


def test_program_model_refuses_a_key_it_cannot_apply():
    config = spec.load_json(spec.HERE / "configs" / "olmo_1b.json")
    config["model"]["norm_eps"] = 1e-5
    with pytest.raises(ValueError, match="norm_eps"):
        run.program_model(config)
