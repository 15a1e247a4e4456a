"""Unified model configuration for the 10 assigned architectures."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # 'dense' | 'moe' | 'ssm' | 'hybrid' | 'encdec' | 'vlm'
    num_layers: int
    d_model: int
    vocab: int
    # attention
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0            # 0 -> d_model // n_heads
    d_ff: int = 0
    sliding_window: int = 0      # 0 = full attention
    rope_theta: float = 10_000.0
    # MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    expert_ff: int = 0           # routed expert hidden dim
    capacity_factor: float = 1.25
    # SSM (mamba1/mamba2)
    ssm_state: int = 0
    d_inner: int = 0             # 0 -> 2 * d_model
    conv_width: int = 4
    dt_rank: int = 0             # mamba1; 0 -> ceil(d_model / 16)
    ssm_head_dim: int = 64       # mamba2
    ssm_chunk: int = 128
    # XLA time-scan chunking: unroll this many recurrence steps per scan
    # iteration so the chain fuses (0 = plain per-step scan); the Pallas
    # ssm_scan kernel (use_flash) supersedes this on TPU
    ssm_time_chunk: int = 0
    # hybrid (zamba2): one weight-tied attention block applied every k layers
    shared_attn_every: int = 0
    # enc-dec
    encoder_layers: int = 0
    # modality frontend stubs ([audio]/[vlm]): prepended precomputed embeds
    num_prefix_embeds: int = 0
    # attention memory control: process queries in chunks of this size when
    # S > 2*chunk (exact, O(S*chunk) memory; SWA also slices the KV range)
    attn_q_chunk: int = 1024
    # decode MoE: route the whole (B*S) token stream as one group (EP
    # all-to-all) instead of per-row capacity — see layers.moe
    moe_group_decode: bool = False
    # fused cross-entropy: never materialize (B, S, V) logits; process the
    # sequence in chunks of this size (0 = off)
    ce_seq_chunk: int = 0
    # attention batch re-sharding: run attention with the batch sharded over
    # BOTH (data, model) and heads replicated — removes contraction-dim TP
    # all-reduces for archs whose head count does not divide the model axis
    attn_batch_shard: bool = False
    # FSDP: shard weights' embed dim over 'data' (ZeRO-3).  Models whose
    # (params + optimizer state) fit replicated can turn this off to remove
    # the per-layer all-gathers entirely.
    fsdp: bool = True
    # numerics / lowering
    dtype: str = "bfloat16"
    scan_layers: bool = True     # scan over layers (False = unrolled, used by
                                 # the dry-run for exact cost_analysis)
    use_flash: Union[bool, str] = False  # route attention / the SSM scan
                                 # through the Pallas kernels (True: Mosaic,
                                 # kernels.ops.INTERPRET: the interpreter)
    remat: bool = True

    # ---- derived -----------------------------------------------------------
    @property
    def dtype_jnp(self):
        return jnp.dtype(self.dtype)

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def inner(self) -> int:
        return self.d_inner or 2 * self.d_model

    @property
    def dtr(self) -> int:
        return self.dt_rank or -(-self.d_model // 16)

    @property
    def n_ssm_heads(self) -> int:
        return self.inner // self.ssm_head_dim

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""
    name: str                    # train_4k | prefill_32k | decode_32k | long_500k
    seq_len: int
    global_batch: int
    kind: str                    # 'train' | 'prefill' | 'decode'


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}
