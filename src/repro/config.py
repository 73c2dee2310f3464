"""Config system: dataclasses describing models, shapes, meshes, and runtime.

Every assigned architecture is expressed as a `ModelConfig`; the four assigned
input shapes are `ShapeConfig`s. `RuntimeConfig` carries implementation
switches (remat policy, quantization format, ...) that the perf hillclimb
iterates on without touching model definitions; whether kernels run as
compiled Pallas or as the XLA reference follows the backend.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 0
    experts_per_token: int = 0
    d_ff: int = 0                       # per-expert hidden
    shared_expert: bool = False         # llama4-style shared expert
    capacity_factor: float = 1.25
    router_z_loss: float = 1e-3
    aux_loss: float = 1e-2


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    state_dim: int = 0                  # N (ssm_state)
    conv_width: int = 4
    head_dim: int = 64                  # P
    num_heads: int = 0                  # derived if 0: expand*d_model//head_dim
    expand: int = 2
    chunk_size: int = 128
    ngroups: int = 1


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                         # transformer | moe | mamba2 | hybrid | whisper | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                   # 0 -> d_model // num_heads
    # attention behaviour
    qkv_bias: bool = False
    rope_theta: float = 1e4
    sliding_window: int = 0             # 0 = full attention
    local_global_pattern: int = 0       # gemma2: every Nth layer global, rest local
    attn_logit_softcap: float = 0.0
    final_logit_softcap: float = 0.0
    use_mrope: bool = False             # qwen2-vl M-RoPE
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)
    # mlp / norm
    act_fn: str = "silu"                # silu (SwiGLU) | gelu (GeGLU)
    norm_eps: float = 1e-6
    post_block_norm: bool = False       # gemma2 post-norms
    tie_embeddings: bool = False
    # substructures
    moe: MoEConfig = MoEConfig()
    ssm: SSMConfig = SSMConfig()
    # hybrid (zamba2): one shared attention block every `attn_every` layers
    attn_every: int = 0
    num_shared_attn_sets: int = 2
    # whisper
    encoder_layers: int = 0
    num_audio_frames: int = 1500
    # vlm stub frontend
    num_vision_patches: int = 0
    # sub-quadratic? controls long_500k applicability
    subquadratic: bool = False

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.num_heads if self.num_heads else 0

    @property
    def ssm_heads(self) -> int:
        s = self.ssm
        return s.num_heads or (s.expand * self.d_model) // s.head_dim

    def param_count(self) -> int:
        """Analytic parameter count (matches init_params; verified in tests)."""
        d, v = self.d_model, self.vocab_size
        hd = self.resolved_head_dim
        emb = v * d * (1 if self.tie_embeddings else 2)
        attn = d * (self.num_heads * hd) + 2 * d * (self.num_kv_heads * hd) \
            + (self.num_heads * hd) * d
        if self.qkv_bias:
            attn += (self.num_heads + 2 * self.num_kv_heads) * hd
        dense_mlp = 3 * d * self.d_ff
        norms = 2 * d
        if self.family in ("transformer", "vlm"):
            per_layer = attn + dense_mlp + norms
            return emb + self.num_layers * per_layer + d
        if self.family == "moe":
            m = self.moe
            moe_mlp = m.num_experts * 3 * d * m.d_ff + d * m.num_experts
            if m.shared_expert:
                moe_mlp += 3 * d * m.d_ff
            per_layer = attn + moe_mlp + norms
            return emb + self.num_layers * per_layer + d
        if self.family == "mamba2":
            return emb + self.num_layers * self._mamba_block_params() + d
        if self.family == "hybrid":
            n_attn_sets = self.num_shared_attn_sets
            n_attn_applied = self.num_attn_layers()
            n_mamba = self.num_layers - n_attn_applied
            shared = n_attn_sets * (attn + dense_mlp + norms)
            return emb + n_mamba * self._mamba_block_params() + shared + d
        if self.family == "whisper":
            enc = self.encoder_layers * (attn + dense_mlp + norms)
            cross = self.num_layers * (attn + d)  # cross-attn + its norm
            dec = self.num_layers * (attn + dense_mlp + norms)
            return emb + enc + dec + cross + 2 * d
        raise ValueError(self.family)

    def _mamba_block_params(self) -> int:
        d = self.d_model
        s = self.ssm
        d_in = s.expand * d
        nh = self.ssm_heads
        conv_dim = d_in + 2 * s.ngroups * s.state_dim
        in_proj = d * (2 * d_in + 2 * s.ngroups * s.state_dim + nh)
        conv = conv_width_params(conv_dim, s.conv_width)
        out_proj = d_in * d
        extras = nh * 2 + d_in + d  # A_log, D, gate-norm, block norm
        return in_proj + conv + out_proj + extras

    def num_attn_layers(self) -> int:
        """Hybrid: how many layers are (shared) attention applications."""
        if self.family != "hybrid" or not self.attn_every:
            return 0
        return self.num_layers // self.attn_every

    def active_param_count(self) -> int:
        """Active params per token (MoE counts only routed experts)."""
        if self.family != "moe":
            return self.param_count()
        m = self.moe
        d = self.d_model
        total = self.param_count()
        inactive = self.num_layers * (m.num_experts - m.experts_per_token) * 3 * d * m.d_ff
        return total - inactive


def conv_width_params(conv_dim: int, width: int) -> int:
    return conv_dim * width + conv_dim


# ---------------------------------------------------------------------------
# Shapes
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                           # train | prefill | decode


TRAIN_4K = ShapeConfig("train_4k", 4096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524288, 1, "decode")

ALL_SHAPES = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
SHAPES_BY_NAME = {s.name: s for s in ALL_SHAPES}


def applicable_shapes(model: ModelConfig):
    """Assignment rules: long_500k only for sub-quadratic archs."""
    out = [TRAIN_4K, PREFILL_32K, DECODE_32K]
    if model.subquadratic:
        out.append(LONG_500K)
    return out


# ---------------------------------------------------------------------------
# Runtime switches (hillclimbing surface)
# ---------------------------------------------------------------------------


def platform_kernels() -> bool:
    """The platform picks the kernel path: compiled Pallas kernels on a TPU
    backend, the XLA reference everywhere else (Pallas has no compiled CPU
    lowering, and interpret mode is a test tool, not a serving path)."""
    import jax
    return jax.default_backend() == "tpu"


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    # Pallas kernels vs XLA reference paths; resolved from the backend at
    # construction unless a caller (a kernel test, a reference run) says
    use_pallas: bool = dataclasses.field(default_factory=platform_kernels)
    interpret: bool = False             # Pallas interpret mode (CPU kernel tests)
    quant_format: str = "bf16"          # bf16 | q8 | q4 — serving weight format
    kv_cache_dtype: str = "bf16"        # bf16 | int8
    remat_policy: str = "full"          # full | save_dots | none
    attn_chunk: int = 512               # XLA chunked-attention kv block
    xent_chunk: int = 32768             # chunked cross-entropy vocab block
    scan_layers: bool = True
    grad_compression: str = "none"      # none | int8
    decode_seq_shard: bool = True       # shard KV cache sequence dim over `model`
    param_dtype: str = "bf16"
    matmul_precision: str = "default"
    moe_dispatch: str = "scatter"       # scatter | onehot


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    grad_clip: float = 1.0
    checkpoint_every: int = 50
    checkpoint_dir: str = "/tmp/repro_ckpt"
    keep_checkpoints: int = 3
    seed: int = 0
