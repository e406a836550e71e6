"""Model / shape / run configuration schema.

Every assigned architecture is a ``ModelConfig`` instance in its own file
under ``repro/configs/``; shapes (seq_len x global_batch x step kind) come
from the shared SHAPES registry. ``reduced()`` derives the smoke-test
variant of any config (same family, tiny dims).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from repro.core.cim_linear import CIMConfig


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int                    # per-expert hidden
    n_shared: int = 0            # always-on shared experts
    capacity_factor: float = 1.25
    n_dense_layers: int = 0      # leading dense-FFN layers (deepseek: 3)
    dense_d_ff: int = 0
    router_scale: bool = True    # normalize top-k gate weights


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    kind: str = "mamba2"         # mamba2 | xlstm
    d_state: int = 64
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    chunk: int = 256             # SSD / chunkwise-mLSTM chunk length
    slstm_every: int = 8         # xlstm: every Nth block is sLSTM
    n_slstm_heads: int = 4


@dataclasses.dataclass(frozen=True)
class VisionConfig:
    """A native-resolution vision tower (family ``vit``): the learned
    position table is ``pos_grid`` x ``pos_grid`` and is resized to each
    image's patch grid; ``merge`` x ``merge`` patch neighbourhoods merge
    into one token, which the projector maps to ``out_dim``."""
    pos_grid: int = 64
    merge: int = 2
    out_dim: int = 2048


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # transformer | xlstm | zamba2 | whisper | llava | vit | resnet
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0            # 0 -> d_model // n_heads
    norm: str = "rmsnorm"        # rmsnorm | layernorm | nonparam_ln
    act: str = "swiglu"          # swiglu | gelu
    qk_norm: bool = False
    rope_theta: float = 10000.0
    max_seq: int = 131072
    tie_embeddings: bool = False
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    vision: Optional[VisionConfig] = None   # vit: the tower's own geometry
    attn_every: int = 0          # zamba2: shared attn block every N ssm blocks
    enc_layers: int = 0          # whisper encoder layers
    n_frontend_tokens: int = 0   # vlm/audio stub tokens (576 patches / 1500 frames)
    frontend_dim: int = 0        # stub embedding dim (defaults to d_model)
    conv_frontend: bool = False  # real conv frontend (CIM conv kernel) vs stub
    patch_size: int = 0          # llava conv frontend / vit: square patch edge
    cim: CIMConfig = dataclasses.field(default_factory=CIMConfig)
    cim_lm_head: bool = False    # also CIM-quantize the LM head
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    remat: bool = True
    scan_layers: bool = True
    attn_chunk: int = 2048       # KV-chunked (flash-style) attention; 0=off
    flash_decode: bool = False   # shard_map seq-parallel decode attention (opt-in; §Perf)
    kv_cache_dtype: str = "bf16" # bf16 | int8 (per-(token,head) scales)
    moe_impl: str = "jit"        # jit (auto-SPMD baseline) | auto (EP shard_map; §Perf)
    sub_quadratic: bool = False  # supports long_500k decode

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class Shape:
    name: str
    kind: str                    # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES = {
    "train_4k": Shape("train_4k", "train", 4096, 256),
    "prefill_32k": Shape("prefill_32k", "prefill", 32768, 32),
    "decode_32k": Shape("decode_32k", "decode", 32768, 128),
    "long_500k": Shape("long_500k", "decode", 524288, 1),
}


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """Training/serving runtime knobs (distribution + optimization)."""
    microbatch: int = 0          # per-device microbatch (0 = auto/no accum)
    accum_steps: int = 1         # gradient accumulation steps
    accum_unroll: bool = False   # unroll the accum loop (HLO accounting)
    fsdp: bool = False           # shard params/opt over the data axis too
    optimizer: str = "adamw"     # adamw | adafactor | sgdm
    opt_state_dtype: str = "float32"
    lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    grad_compress: bool = False  # int8 reduce-scatter/all-gather w/ error fb
    label_smoothing: float = 0.0
    seed: int = 0
    checkpoint_dir: str = "/tmp/repro_ckpt"
    checkpoint_every: int = 200
    async_checkpoint: bool = True
