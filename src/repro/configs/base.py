"""Config system: model architecture + input-shape registries.

Every assigned architecture is a ``ModelConfig`` in its own module
(``repro/configs/<arch>.py``) registered under its ``--arch`` id; every
assigned input shape is a ``ShapeConfig``.  ``smoke()`` derives the reduced
same-family config used by CPU smoke tests.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

ARCH_REGISTRY: Dict[str, "ModelConfig"] = {}


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    arch_id: str
    family: str                 # dense | moe | mamba_hybrid | rwkv | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None          # None -> d_model // n_heads
    qkv_bias: bool = False
    rope_theta: float = 1_000_000.0
    tie_embeddings: bool = False
    # --- MoE ---
    num_experts: int = 0
    top_k: int = 0
    moe_every: int = 1          # 1: every layer MoE; 2: interleaved (llama4)
    shared_expert: bool = False
    capacity_factor: float = 1.25
    # --- SSM / hybrid (zamba2, rwkv6) ---
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_groups: int = 1         # mamba2 B/C groups (heads h use h // (H/G))
    # zamba2: a shared attention block runs before the Mamba2 mixer of each
    # of these layers, blocks taken in turn; a LoRA of this rank and a
    # linear of its own at each call
    hybrid_layers: Tuple[int, ...] = ()
    shared_blocks: int = 0
    adapter_rank: int = 0
    norm_eps: float = 1e-6
    # --- encoder-decoder (whisper) ---
    enc_layers: int = 0
    dec_layers: int = 0
    # --- modality frontend stubs ---
    frontend: str = "none"      # none | audio | vit
    num_patches: int = 0        # vlm: prepended patch embeddings
    frontend_dim: int = 0       # stub embedding dim (== d_model after proj)
    # --- runtime ---
    dtype: str = "bfloat16"
    attn_impl: str = "blocked"  # reference | blocked | flash
    attn_chunk: int = 1024      # blocked-attention kv tile
    scan_layers: bool = True
    remat: str = "full"         # none | full | dots
    sub_quadratic: bool = False # may run long_500k

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up to a 128-multiple so the embedding / lm_head
        shard over the model axis (Megatron-style padding; granite's 49155
        and whisper's 51865 otherwise replicate the head — measured at
        ~37% of the training-step flops).  Logits beyond ``vocab`` are
        masked to -inf in the loss/sampler."""
        return -(-self.vocab // 128) * 128

    @property
    def d_inner(self) -> int:   # mamba2 inner width
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # ----------------------------------------------------------- smoke form
    def smoke(self) -> "ModelConfig":
        """Reduced same-family config for CPU smoke tests."""
        kw = dict(
            n_layers=min(self.n_layers, 2),
            d_model=128,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2) if self.n_kv_heads < self.n_heads else 4,
            head_dim=32,
            d_ff=256,
            vocab=512,
            dtype="float32",
            remat="none",
        )
        if self.num_experts:
            kw.update(num_experts=4, top_k=min(self.top_k, 2), d_ff=64)
        if self.ssm_state:
            kw.update(ssm_state=16, ssm_head_dim=16)
        if self.hybrid_layers:
            # blocks A, B, A at layers 2, 4, 7: every adapter its own
            kw.update(n_layers=8, hybrid_layers=(2, 4, 7), d_model=64,
                      head_dim=2 * 64 // 4, d_ff=128, ssm_head_dim=8,
                      adapter_rank=8)
        if self.enc_layers:
            kw.update(enc_layers=2, dec_layers=2)
        if self.num_patches:
            kw.update(num_patches=8)
        return self.replace(**kw)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                   # train | prefill | decode

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def register(cfg: ModelConfig) -> ModelConfig:
    ARCH_REGISTRY[cfg.arch_id] = cfg
    return cfg


def get_arch(arch_id: str) -> ModelConfig:
    from . import _load_all          # lazy-populate the registry
    _load_all()
    return ARCH_REGISTRY[arch_id]


def all_archs() -> Tuple[str, ...]:
    from . import _load_all
    _load_all()
    return tuple(sorted(ARCH_REGISTRY))


def cells(arch_id: str) -> Tuple[Tuple[str, str], ...]:
    """All (arch, shape) cells for an arch, honouring long_500k skips."""
    cfg = get_arch(arch_id)
    out = []
    for s in SHAPES.values():
        if s.name == "long_500k" and not cfg.sub_quadratic:
            continue            # pure full-attention archs skip (DESIGN §4)
        out.append((arch_id, s.name))
    return tuple(out)
