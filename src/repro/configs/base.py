"""Config system: one frozen dataclass per architecture.

Every served architecture gets a module in repro.configs exposing CONFIG;
``get_config(name)`` resolves them, ``scaled_down()`` produces the reduced
smoke-test variant (same family/topology, tiny dims).
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | ssm | hybrid
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    rope_theta: float = 10_000.0
    norm: str = "rmsnorm"        # rmsnorm | layernorm | nonparam_ln
    act: str = "silu"            # silu | gelu
    mlp_kind: str = "swiglu"     # swiglu | gelu_mlp
    # SSM (mamba2)
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_chunk: int = 128
    ssm_groups: int = 1          # B/C groups; head n reads group n // (heads/groups)
    ssm_conv_bias: bool = False
    # hybrid (zamba2): the layers whose Mamba2 input also takes a call of a
    # weight-shared attention+MLP block; call j uses block j % n_mem_blocks,
    # its own MLP adapter (rank adapter_rank, 0 = none) and output projection
    hybrid_layer_ids: Tuple[int, ...] = ()
    n_mem_blocks: int = 1
    adapter_rank: int = 0
    # rwkv6
    rwkv_chunk: int = 64
    tie_embeddings: bool = True
    dtype: str = "bfloat16"
    remat: str = "full"          # full | none  (activation checkpoint policy)
    attn_chunk: int = 1024       # online-softmax KV/Q chunk for long prefill
    loss_chunk: int = 512        # fused unembed+CE sequence chunk
    cache_quant: bool = False    # int8 KV cache (serving memory-term knob)

    def __post_init__(self):
        # a configuration file gives the ids as a list; keep the config hashable
        object.__setattr__(self, "hybrid_layer_ids",
                           tuple(self.hybrid_layer_ids))

    # ------------------------------------------------------------------ utils
    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def hybrid_ids(self) -> Tuple[int, ...]:
        """The hybrid layers present at this depth."""
        return tuple(i for i in self.hybrid_layer_ids if i < self.n_layers)

    def scaled_down(self) -> "ModelConfig":
        """Reduced same-family config for CPU smoke tests."""
        n_layers = min(self.n_layers, 4)
        if len(self.hybrid_layer_ids) >= 2:  # two calls: both blocks of two
            n_layers = min(self.n_layers, self.hybrid_layer_ids[1] + 1)
        return dataclasses.replace(
            self,
            n_layers=n_layers,
            d_model=128,
            n_heads=4,
            n_kv_heads=min(4, max(1, self.n_kv_heads * 4 // self.n_heads)),
            head_dim=32,
            d_ff=256,
            vocab_size=512,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_chunk=16,
            rwkv_chunk=16,
            attn_chunk=32,
            remat="none",
        )

    def param_count(self) -> int:
        """Analytic parameter count."""
        d, f, L = self.d_model, self.d_ff, self.n_layers
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        if self.family == "ssm":  # rwkv6
            # time-mix: wr,wk,wv,wg,wo (5·d²) + decay LoRA (2·64·d);
            # channel-mix: wr (d²) + wk/wv (2·d·f)
            per = 6 * d * d + 2 * d * f + 128 * d
            return emb + L * per
        attn = d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
        mlp = 3 * d * f if self.mlp_kind == "swiglu" else 2 * d * f
        per = attn + mlp
        if self.family == "hybrid":  # exact: every leaf of models/hybrid.py
            di, nh = self.d_inner, self.ssm_heads
            conv = di + 2 * self.ssm_groups * self.ssm_state
            mamba = (d * (di + conv + nh) + self.ssm_conv * conv
                     + conv * self.ssm_conv_bias + 3 * nh + di + di * d + d)
            shared = (2 * d * self.q_dim + 4 * d * self.kv_dim
                      + self.q_dim * d + 3 * d * f + 3 * d)
            call = d * d + self.adapter_rank * (d + 2 * f)
            return (emb + d + L * mamba + self.n_mem_blocks * shared
                    + len(self.hybrid_ids) * call)
        return emb + L * per


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


def get_config(name: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{name}")
    return mod.CONFIG

