"""Configuration system.

``ModelConfig`` fully describes a backbone (any of the 6 assigned families) +
its NanoEdge adaptation. ``InputShape`` describes a workload. The registry in
``repro.configs`` maps ``--arch`` ids to config builders.

All assigned-architecture configs cite their source in the module docstring of
their own file under ``repro/configs/``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field, replace
from typing import Optional, Tuple


@dataclass(frozen=True)
class MoEConfig:
    """A dropless expert layer, of which this chip may hold a share.

    The router scores all ``n_experts``: ``softmax`` over them, or
    ``sigmoid`` of each, to whose scores ``score_bias`` adds a learned
    correction bias for the choice of the ``top_k`` only (DeepSeek-V3's
    ``noaux_tc``). A chosen expert's weight is its score, renormalised over
    the chosen ones, times ``routed_scale``. The chip holds experts
    ``[expert_first, expert_first + experts_held)`` (``experts_held`` 0:
    all of them) and computes their part of the result; the absent
    experts' part is left out.
    """

    n_experts: int = 8
    top_k: int = 2
    router_aux_weight: float = 0.01  # load-balance loss weight (frozen backbone -> reported only)
    shared_d_ff: int = 0  # width of the shared experts, one SwiGLU for every token (0 = none)
    scoring: str = "softmax"  # softmax | sigmoid
    score_bias: bool = False
    routed_scale: float = 1.0
    expert_first: int = 0
    experts_held: int = 0

    @property
    def held(self) -> int:
        return self.experts_held or self.n_experts


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434), no query LoRA.

    q = x W_q splits per head into ``qk_nope_head_dim`` + ``qk_rope_head_dim``;
    [c_kv; k_rope] = x W_kva, c_kv RMS-normed, k_rope rotated and shared by
    all heads; [k_nope; v] = c_kv W_kvb per head. Scores read the q/k width
    ``qk_nope_head_dim + qk_rope_head_dim``, values ``v_head_dim``.
    """

    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 (SSD — state space duality, arXiv:2405.21060)."""

    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64       # SSD multi-head: d_inner / head_dim heads
    chunk_size: int = 256    # chunked-scan block length (TPU MXU-friendly duality form)
    dt_min: float = 0.001
    dt_max: float = 0.1


@dataclass(frozen=True)
class RGLRUConfig:
    """RG-LRU recurrent block (Griffin/RecurrentGemma, arXiv:2402.19427)."""

    d_rnn: int = 0            # recurrence width (0 -> d_model)
    conv_width: int = 4
    block_pattern: Tuple[str, ...] = ("rec", "rec", "attn")  # 1:2 attn:recurrent
    local_window: int = 2048  # local-attention window of the attn layers


@dataclass(frozen=True)
class AdapterConfig:
    """NanoEdge / NanoAdapter configuration (the paper's contribution)."""

    rank: int = 64
    alpha: float = 128.0
    modalities: Tuple[str, ...] = ("text",)  # ("text",), or ("text", "image")
    dropout: float = 0.0
    dtype: str = "float32"   # adapters train in fp32 (tiny), backbone runs bf16


@dataclass(frozen=True)
class ModelConfig:
    name: str = "unnamed"
    family: str = "dense"          # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int = 2
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    head_dim: int = 0              # 0 -> d_model // n_heads
    d_ff: int = 512
    vocab_size: int = 1024
    max_seq_len: int = 8192

    # attention / positions
    pos_type: str = "rope"         # rope | mrope | learned | none
    rope_theta: float = 10000.0
    mrope_sections: Tuple[int, ...] = ()   # qwen2-vl M-RoPE (t, h, w) dims
    qkv_bias: bool = False
    sliding_window: Optional[int] = None   # SWA window (h2o-danube: 4096)
    logit_softcap: float = 0.0             # grok-style attn-logit soft cap (0 = off)

    # block structure
    norm: str = "rmsnorm"          # rmsnorm | layernorm
    norm_eps: float = 1e-6         # RMSNorm epsilon
    act: str = "swiglu"            # swiglu | geglu | gelu
    tie_embeddings: bool = False
    parallel_block: bool = False   # parallel attn+ffn residual (grok-style off; kept for ext.)

    # sub-family configs
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None         # latent attention in place of per-head K/V
    n_dense_layers: int = 0        # leading dense layers before the scanned MoE stack
    dense_d_ff: int = 0            # their MLP width (0 -> d_ff)
    ssm: Optional[SSMConfig] = None
    rglru: Optional[RGLRUConfig] = None

    # encoder-decoder (audio family, whisper-style)
    n_enc_layers: int = 0
    enc_seq_len: int = 1500        # fixed encoder memory length (frames)

    # modality frontend stub (vlm/audio): incoming embedding width before connector
    frontend_dim: int = 0          # 0 -> no image/audio stream

    # NanoEdge
    adapter: AdapterConfig = field(default_factory=AdapterConfig)

    # numerics / execution
    dtype: str = "bfloat16"
    remat: bool = True             # activation-checkpoint the scanned layer body
    scan_layers: bool = True       # lax.scan over stacked layer params
    use_pallas: bool = False       # route hot ops through Pallas kernels (TPU)
    attn_chunk: Optional[int] = None   # blockwise-softmax query chunking (jnp path);
                                       # bounds live logits to (B, H, chunk, S)
    loss_chunk: Optional[int] = None   # blockwise cross-entropy (bounds (B, chunk, V) logits)
    seq_parallel: bool = False         # Megatron-SP: residual stream sequence-sharded
                                       # over the model axis; AR -> AG/RS pairs (dense/vlm/moe)
    ctx_parallel_attn: bool = False    # shard QUERY sequence over model when heads don't
                                       # divide the axis (prefill-only: the bwd pass of this
                                       # layout regresses — EXPERIMENTS §Perf qwen1.5)

    # sub-quadratic marker (decides long_500k eligibility)
    @property
    def subquadratic(self) -> bool:
        if self.family in ("ssm", "hybrid"):
            return True
        return self.sliding_window is not None

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.n_heads

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // max(self.n_kv_heads, 1)

    def with_(self, **kw) -> "ModelConfig":
        """A copy with ``kw`` replaced; a dict given for a sub-config (``moe``,
        ``mla``, ...) replaces those fields of it."""
        for k, v in kw.items():
            cur = getattr(self, k)
            if isinstance(v, dict) and dataclasses.is_dataclass(cur):
                kw[k] = replace(cur, **v)
        return replace(self, **kw)


def reduced(cfg: ModelConfig, **overrides) -> ModelConfig:
    """Smoke-test variant of the same family: ≤2 layers, d_model ≤ 512, ≤4 experts.

    Keeps every structural switch (family, pos_type, bias, window, pattern)
    identical so the smoke test exercises the same code path as the full config.
    """
    d_model = min(cfg.d_model, 256)
    n_heads = min(cfg.n_heads, 4)
    n_kv = max(1, min(cfg.n_kv_heads, n_heads))
    # preserve the GQA grouping ratio where possible
    if cfg.n_kv_heads < cfg.n_heads:
        n_kv = max(1, n_heads // max(1, cfg.q_per_kv))
    head_dim = d_model // n_heads
    kw = dict(
        n_layers=min(cfg.n_layers, 2),
        d_model=d_model,
        n_heads=n_heads,
        n_kv_heads=n_kv,
        head_dim=head_dim,
        d_ff=min(cfg.d_ff, 512) if cfg.d_ff else 0,
        vocab_size=min(cfg.vocab_size, 512),
        max_seq_len=min(cfg.max_seq_len, 512),
        sliding_window=min(cfg.sliding_window, 64) if cfg.sliding_window else None,
        mrope_sections=(head_dim // 4, head_dim // 8, head_dim // 8) if cfg.mrope_sections else (),
        dtype="float32",
        remat=False,
        adapter=dataclasses.replace(cfg.adapter, rank=4, alpha=8.0),
    )
    if cfg.moe is not None:
        n_e = min(cfg.moe.n_experts, 4)
        held = min(cfg.moe.experts_held, n_e // 2) if cfg.moe.experts_held else 0
        kw["moe"] = dataclasses.replace(
            cfg.moe, n_experts=n_e, top_k=min(cfg.moe.top_k, 2),
            shared_d_ff=min(cfg.moe.shared_d_ff, 256) if cfg.moe.shared_d_ff else 0,
            expert_first=min(cfg.moe.expert_first, n_e - max(held, 1)), experts_held=held,
        )
    if cfg.mla is not None:
        kw["mla"] = dataclasses.replace(cfg.mla, kv_lora_rank=32, qk_nope_head_dim=16,
                                        qk_rope_head_dim=8, v_head_dim=16)
    if cfg.n_dense_layers:
        kw["n_dense_layers"] = 1
        kw["n_layers"] = min(cfg.n_layers, 3)   # the dense layer and two MoE layers
        kw["dense_d_ff"] = min(cfg.dense_d_ff, 512)
    if cfg.ssm is not None:
        kw["ssm"] = dataclasses.replace(cfg.ssm, d_state=16, head_dim=32, chunk_size=32)
    if cfg.rglru is not None:
        kw["rglru"] = dataclasses.replace(
            cfg.rglru, d_rnn=0, local_window=min(cfg.rglru.local_window, 64)
        )
        kw["n_layers"] = 3  # one full (rec, rec, attn) block
    if cfg.n_enc_layers:
        kw["n_enc_layers"] = min(cfg.n_enc_layers, 2)
        kw["enc_seq_len"] = min(cfg.enc_seq_len, 64)
    if cfg.frontend_dim:
        kw["frontend_dim"] = min(cfg.frontend_dim, 128)
    kw.update(overrides)
    return replace(cfg, **kw)


@dataclass(frozen=True)
class InputShape:
    """A workload: (kind, seq_len, global_batch)."""

    name: str
    kind: str          # train | prefill | decode
    seq_len: int
    global_batch: int


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", "train", 4_096, 256),
    "prefill_32k": InputShape("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": InputShape("decode_32k", "decode", 32_768, 128),
    "long_500k": InputShape("long_500k", "decode", 524_288, 1),
}
