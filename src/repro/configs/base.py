"""Config system: model / shape / mesh / run configs and the arch registry.

Every assigned architecture has one file in this package defining an exact
``ModelConfig`` (`full_config()`) plus a reduced config of the same family
(`smoke_config()`) used by CPU smoke tests.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Tuple


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    num_shared_experts: int = 0
    capacity_factor: float = 1.25
    # d_ff of each routed expert (may differ from the dense d_ff)
    d_expert: int = 0
    router_jitter: float = 0.0
    # expert-parallel padding: experts [num_real:] are zero-weight and their
    # router logits are masked — bit-exact with the unpadded model (0 = none)
    num_real_experts: int = 0

    @property
    def real_experts(self) -> int:
        return self.num_real_experts or self.num_experts


@dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    head_dim: int = 64
    expand: int = 2
    conv_kernel: int = 4
    n_groups: int = 1
    chunk_size: int = 256
    dt_min: float = 0.001
    dt_max: float = 0.1


@dataclass(frozen=True)
class HybridConfig:
    """Zamba2-style: groups of SSM layers with a shared attention block."""
    ssm_per_group: int = 5
    num_groups: int = 13
    tail_ssm_layers: int = 3

    @property
    def total_layers(self) -> int:
        return self.num_groups * (self.ssm_per_group + 1) + self.tail_ssm_layers


@dataclass(frozen=True)
class EncDecConfig:
    enc_layers: int = 12
    # stubbed audio frontend: precomputed frame embeddings
    num_frames: int = 1500


@dataclass(frozen=True)
class FrontendConfig:
    """Modality frontend stub: precomputed embeddings injected as inputs."""
    kind: str = "none"  # none | audio_stub | vision_stub
    num_embeds: int = 0  # frames or patches provided by input_specs()


@dataclass(frozen=True)
class ModelConfig:
    arch: str
    family: str  # dense | moe | ssm | hybrid | encdec | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // num_heads
    qk_norm: bool = False
    tie_embeddings: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    # Granite-style scalars (1.0 = disabled)
    embedding_multiplier: float = 1.0
    logits_scaling: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0  # 0 -> 1/sqrt(head_dim)
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    hybrid: Optional[HybridConfig] = None
    encdec: Optional[EncDecConfig] = None
    frontend: FrontendConfig = field(default_factory=FrontendConfig)
    dtype: str = "bfloat16"
    source: str = ""  # provenance tag: [hf:...; tier]

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.num_heads if self.num_heads else 0

    @property
    def attn_free(self) -> bool:
        return self.family == "ssm"

    @property
    def subquadratic(self) -> bool:
        """True if the arch can run the 500k-token long-context decode shape."""
        return self.family in ("ssm", "hybrid")

    def param_count(self) -> int:
        """Analytic parameter count (used by roofline MODEL_FLOPS and memory checks)."""
        d, hd = self.d_model, self.resolved_head_dim
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)

        def attn_params() -> int:
            q = d * self.num_heads * hd
            kv = 2 * d * self.num_kv_heads * hd
            o = self.num_heads * hd * d
            qknorm = 2 * hd if self.qk_norm else 0
            return q + kv + o + qknorm

        def dense_ffn(ff: int) -> int:
            return 3 * d * ff  # SwiGLU: gate, up, down

        def block_norms() -> int:
            return 2 * d

        if self.family == "ssm":
            s = self.ssm
            d_in = s.expand * d
            nheads = d_in // s.head_dim
            per = (
                d * (2 * d_in + 2 * s.n_groups * s.d_state + nheads)  # in_proj
                + s.conv_kernel * (d_in + 2 * s.n_groups * s.d_state)  # conv
                + nheads * 2  # A_log, dt_bias
                + d_in  # norm gate
                + d_in * d  # out_proj
                + d  # pre-norm
            )
            return emb + self.num_layers * per + d
        if self.family == "hybrid":
            h = self.hybrid
            s = self.ssm
            d_in = s.expand * d
            nheads = d_in // s.head_dim
            ssm_per = (
                d * (2 * d_in + 2 * s.n_groups * s.d_state + nheads)
                + s.conv_kernel * (d_in + 2 * s.n_groups * s.d_state)
                + nheads * 2 + d_in + d_in * d + d
            )
            n_ssm = h.num_groups * h.ssm_per_group + h.tail_ssm_layers
            shared = attn_params() + dense_ffn(self.d_ff) + block_norms()
            return emb + n_ssm * ssm_per + shared + d
        if self.family == "moe":
            m = self.moe
            d_e = m.d_expert or self.d_ff
            router = d * m.num_experts
            experts = m.num_experts * 3 * d * d_e
            shared = m.num_shared_experts * 3 * d * d_e
            per = attn_params() + router + experts + shared + block_norms()
            return emb + self.num_layers * per + d
        if self.family == "encdec":
            e = self.encdec
            enc_per = attn_params() + dense_ffn(self.d_ff) + block_norms()
            dec_per = 2 * attn_params() + dense_ffn(self.d_ff) + 3 * d
            return emb + e.enc_layers * enc_per + self.num_layers * dec_per + 2 * d
        # dense / vlm
        per = attn_params() + dense_ffn(self.d_ff) + block_norms()
        return emb + self.num_layers * per + d

    def active_param_count(self) -> int:
        """Params touched per token (MoE: routed top-k + shared only)."""
        if self.family != "moe":
            return self.param_count()
        m = self.moe
        d_e = m.d_expert or self.d_ff
        inactive = (m.num_experts - m.top_k) * 3 * self.d_model * d_e
        return self.param_count() - self.num_layers * inactive

    def kv_bytes_per_token(self, bytes_per_el: int = 2) -> int:
        """KV-cache bytes per token across all attention layers."""
        hd = self.resolved_head_dim
        if self.family == "ssm":
            return 0
        if self.family == "hybrid":
            n_attn = self.hybrid.num_groups  # shared block applied once per group
            return 2 * n_attn * self.num_kv_heads * hd * bytes_per_el
        n_attn = self.num_layers
        return 2 * n_attn * self.num_kv_heads * hd * bytes_per_el


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str  # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524288, 1),
}


@dataclass(frozen=True)
class RunConfig:
    """Knobs for a concrete lowering/run of one (arch x shape) cell."""
    remat: bool = True
    param_dtype: str = "bfloat16"
    # chunked-pipeline (MOCAP) knobs
    num_chunks: int = 16
    num_stages: int = 16
    mbkr: bool = True
    # chunks per request that spill to the pair stage (M - p2); 0 -> the
    # p2 with the fewest slots (mbkr.best_p2)
    mbkr_spill_chunks: int = 0
    kv_spill_dtype: str = "bfloat16"  # beyond-paper: int8 spill compression
    remote_attn: str = "qship"  # fetch (paper-faithful) | qship (beyond-paper)
    # attention inner-loop implementation (core.attention registry):
    # "jnp" = pure-jnp online-softmax reference; "pallas" = the flash kernel
    # kernels.ops.chunk_attention (interpret mode off-TPU, Mosaic on TPU)
    attn_backend: str = "jnp"
    # backend-per-source mixing: the POOL-sourced partial states (own-pool
    # scan, fetch'd chunks, the creditor-side qship scan) may run a
    # different backend than the causal self block — e.g. pallas self-block
    # + jnp remote partials. "auto" follows attn_backend; under "pallas"
    # the pool scan is ONE batched slot-grid kernel launch (O(1) in pool
    # depth) instead of one chunk_attention launch per occupied slot;
    # "paged" keeps the single launch but reads KV pages IN PLACE from the
    # page store (scalar-prefetched handle rows + double-buffered async
    # copies — no gather_chunks stack in HBM, DESIGN.md §3.7)
    pool_backend: str = "auto"
    # SSD inner loop for the ssm/hybrid stage programs, same knob pattern:
    # "jnp" = models.ssm.ssd_chunked reference; "pallas" = kernels.ops.ssd
    ssm_backend: str = "jnp"
    # KV page store (repro.kvstore): storage dtype of the per-stage paged
    # pool — "auto" (model dtype, bit-identical to the unpaged pool),
    # "int8" / "fp8" (per-kv-head-scale codec; spill/fetch wires carry the
    # compressed payload, leases count quantized bytes)
    kv_dtype: str = "auto"
    # tokens per KV page; 0 = one page per chunk (rounded down to a divisor
    # of the chunk length otherwise)
    kv_page_tokens: int = 0
    # enable the cold tier: host-offload placement + analytic prefetch off
    # the LBCP plan (kvstore.tiers); serving-path staging via device_put
    kv_offload: bool = False
    # "kv_split": reshape the TP axis into ("kv","qg") so GQA attention is
    # collective-free (beyond-paper perf variant; auto-falls-back when head
    # counts don't divide). "auto": plain 16-way model axis.
    attn_sharding: str = "auto"
    # TP lowering strategy (core.transport / DESIGN.md §3.6): "auto" =
    # GSPMD partial-auto shard_map; "manual" = all mesh axes manual,
    # explicit transport psums in the stage programs.
    tp_lowering: str = "auto"
    # transport registry entry (core.transport): how cross-stage/cross-rank
    # collectives lower. "jax" = jax.lax collectives; future TPU-native
    # qship DMA / cold-streaming transports register here.
    transport: str = "jax"
    # batched fetch (core.remote): "auto" lands all remote chunk-layers in
    # a staging buffer and runs ONE pool_attention launch when the pool
    # backend advertises batched_pool; "off" forces the paper-faithful
    # one-streamed-combine-per-chunk order; "on" requires a batched backend
    fetch_batch: str = "auto"
    partition: str = "uniform"  # uniform | lbcp
    # Megatron-style TP degree is implied by the mesh "model" axis.
    fsdp: bool = True
    grad_accum: int = 1


_REGISTRY: Dict[str, Callable[[], ModelConfig]] = {}
_SMOKE_REGISTRY: Dict[str, Callable[[], ModelConfig]] = {}


def register(name: str, full: Callable[[], ModelConfig], smoke: Callable[[], ModelConfig]) -> None:
    _REGISTRY[name] = full
    _SMOKE_REGISTRY[name] = smoke


def get_config(arch: str) -> ModelConfig:
    _ensure_loaded()
    if arch not in _REGISTRY:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[arch]()


def get_smoke_config(arch: str) -> ModelConfig:
    _ensure_loaded()
    return _SMOKE_REGISTRY[arch]()


def list_archs() -> Tuple[str, ...]:
    _ensure_loaded()
    return tuple(sorted(_REGISTRY))


_LOADED = False


def _ensure_loaded() -> None:
    global _LOADED
    if _LOADED:
        return
    from importlib import import_module

    for mod in (
        "whisper_small", "qwen3_8b", "stablelm_3b", "granite_3_2b", "qwen3_14b",
        "granite_moe_3b_a800m", "qwen2_moe_a2_7b", "llava_next_34b",
        "zamba2_7b", "mamba2_130m",
        # paper-evaluation models (simulator workloads, Fig. 6)
        "llama3_70b", "mistral_123b", "qwen3_235b", "llama3_405b",
    ):
        import_module(f"repro.configs.{mod}")
    _LOADED = True


def replace(cfg, **kw):
    return dataclasses.replace(cfg, **kw)
