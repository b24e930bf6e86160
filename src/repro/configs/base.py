"""Configuration schema for models, input shapes and runs."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

FAMILIES = ("dense", "moe", "ssm", "hybrid", "audio", "vlm", "mlp")
BLOCK_KINDS = ("attn", "moe", "mamba", "slstm", "mlstm")


def pad_vocab(v: int, multiple: int = 256) -> int:
    return ((v + multiple - 1) // multiple) * multiple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    arch_id: str
    family: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None            # default d_model // n_heads
    # --- attention ---
    qkv_bias: bool = False
    rope_theta: float = 1e4
    rope_fraction: float = 1.0                # chatglm3: 0.5 (2d/partial RoPE)
    sliding_window: Optional[int] = None      # mixtral SWA; dense long_500k variant
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    # --- SSM (mamba2 / SSD) ---
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_chunk: int = 128
    # --- hybrid (zamba2): one weight-shared attn block every k mamba blocks
    attn_every: int = 0
    # --- heterogeneous stacks: repeating unit of BLOCK_KINDS ---
    block_pattern: Tuple[str, ...] = ("attn",)
    # --- encoder-decoder (whisper) ---
    is_encoder_decoder: bool = False
    n_encoder_layers: int = 0
    n_audio_frames: int = 1500                # encoder positions: the conv stem's
                                              # output, half the log-mel frames
    n_mels: int = 80                          # log-mel bins the conv stem reads
    max_target_positions: Optional[int] = None
    # --- vlm (phi-3-vision) ---
    n_vision_tokens: int = 0                  # stub patch embeddings
    vision_dim: int = 1024                    # stub frontend embedding width
    # --- numerics / misc ---
    # Chunked cross-entropy: compute the LM head + CE over vocab chunks of
    # this size (0 = off, materialize full logits). Cuts HBM traffic for
    # 100k+ vocabs several-fold (see EXPERIMENTS.md §Perf pair C).
    ce_chunk: int = 0
    # Explicit with_sharding_constraint hints on the MoE dispatch/combine
    # intermediates (keeps the one-hot dispatch tensors token-sharded instead
    # of letting GSPMD replicate them — §Perf pair A). No-op without a mesh.
    shard_hints: bool = False
    # Use the Pallas flash-attention kernels on BOTH the serving and the
    # training path (attend_full / encoder_attend / attend_full_with_cache).
    # Fully differentiable: forward emits the logsumexp residual, reverse
    # mode runs the Pallas dQ and dK/dV kernels, forward mode (the curvature
    # engine's J·v) runs the Pallas JVP pass, and exact-Hessian
    # forward-over-reverse traces use an AD-closed chunked-jnp form (see
    # kernels/flash_ad.py + EXPERIMENTS.md §Perf pair F). Non-block-aligned
    # seq_len is padded to the 128 tile, tail-masked and sliced. Explicit
    # masks and cross-attention keep the jnp `_sdpa` fallback/oracle.
    use_flash_attention: bool = False
    dtype: str = "float32"
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    mlp_act: str = "swiglu"                   # swiglu | gelu
    norm_kind: str = "rmsnorm"                # rmsnorm | layernorm
    source: str = ""                          # citation for the config
    notes: str = ""

    def __post_init__(self):
        assert self.family in FAMILIES, self.family
        for b in self.block_pattern:
            assert b in BLOCK_KINDS, b

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        return pad_vocab(self.vocab_size)

    @property
    def d_inner(self) -> int:                 # mamba2 inner width
        return self.ssm_expand * self.d_model

    @property
    def ssm_n_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks + head)."""
        d, f, V = self.d_model, self.d_ff, self.padded_vocab
        hd, H, KV = self.resolved_head_dim, self.n_heads, self.n_kv_heads
        attn = d * (H * hd) + 2 * d * (KV * hd) + (H * hd) * d
        if self.qkv_bias:
            attn += (H + 2 * KV) * hd
        mlp = 3 * d * f if self.mlp_act == "swiglu" else 2 * d * f
        moe_mlp = self.n_experts * mlp + d * self.n_experts
        din, N = self.d_inner, self.ssm_state
        nh = self.ssm_n_heads if self.ssm_state else 0
        mamba = (
            d * (2 * din + 2 * N + nh)        # in_proj: x, z, B, C, dt
            + self.ssm_conv * din             # depthwise conv
            + din * d                          # out_proj
            + 3 * nh                           # A, D, dt_bias
        ) if self.ssm_state else 0
        mlstm = 4 * d * d + d * d + 2 * d + d * d  # q,k,v,o (+gates, skip proj)
        slstm = 4 * d * d + 4 * (d // max(self.n_heads, 1)) * d + 4 * d

        def block_cost(kind: str) -> int:
            return {
                "attn": attn + mlp + 2 * d,
                "moe": attn + moe_mlp + 2 * d,
                "mamba": mamba + d,
                "mlstm": mlstm + 2 * d,
                "slstm": slstm + 2 * d,
            }[kind]

        n_units = self.n_layers // len(self.block_pattern)
        blocks = n_units * sum(block_cost(k) for k in self.block_pattern)
        if self.family == "hybrid" and self.attn_every:
            blocks += attn + mlp + 2 * d      # ONE shared attention block
        if self.is_encoder_decoder:
            blocks += self.n_encoder_layers * (attn + mlp + 2 * d)
            blocks += self.n_layers // len(self.block_pattern) * (attn + 2 * d)  # cross-attn
            blocks += 3 * self.n_mels * d + 3 * d * d + 2 * d       # conv stem
            blocks += (self.max_target_positions or 0) * d          # learned positions
        emb = V * d
        head = 0 if self.tie_embeddings else V * d
        return emb + blocks + head + d


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                                  # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class HFOptConfig:
    """Optimizer selection + paper hyper-parameters (see core.hf.HFConfig)."""
    name: str = "bicgstab"                    # sgd | momentum | adam | gn_cg | hessian_cg | hybrid_cg | bicgstab
    lr: float = 0.1                            # first-order only
    momentum: float = 0.9
    max_cg_iters: int = 16
    cg_tol: float = 5e-3
    init_damping: float = 1.0
    cg_decay: float = 0.95
    hvp_batch_frac: float = 0.25               # curvature mini-batch fraction
    precondition: bool = False                 # Jacobi preconditioning (all Krylov solvers)
    krylov_backend: str = "tree"               # "tree" (sharded pytrees) | "flat" (fused Pallas)
    # Curvature engine (core.curvature): "naive" | "linearize" | "chunked".
    # "linearize" caches the primal linearization once per outer step;
    # "chunked" adds lax.scan microbatch accumulation of G·v at flat memory
    # (curvature_chunk_size examples per chunk) for Fig. 4-scale hvp batches.
    curvature_mode: str = "linearize"
    curvature_chunk_size: int = 0              # chunked mode: examples per microbatch
    # s-step (communication-avoiding) Krylov solve (core.sstep): sstep_s > 1
    # batches the dot products of s Krylov iterations into one Gram-matrix
    # reduction (1 + ceil(K/s) + E reduces per outer step vs 1 + K + E),
    # with a conditioning guard that falls back to the standard solver.
    # sstep_solver: "auto" (derive from `name`) | "cg" | "bicgstab".
    # sstep_basis picks the chain polynomial: "monomial" (f32 depth budget
    # s≤4 CG / s≤2 Bi-CG-STAB) | "newton" | "chebyshev" (Ritz-parameterized
    # conditioned bases that double usable s — EXPERIMENTS.md §Perf pair G).
    sstep_s: int = 1
    sstep_solver: str = "auto"
    sstep_basis: str = "monomial"
    # Overlapped collective schedule (core.hf HFConfig.overlap):
    # double-buffered s-step cycles (two cycles per Gram reduction), the
    # gradient all-reduce hidden behind the curvature primal build, and
    # paired speculative line-search trials — blocking syncs per outer step
    # drop from 1 + ceil(K/s) + E to ceil(K/2s) + ceil(E/2)
    # (benchmarks/comm_model.py overlap=True, measured by
    # benchmarks/fig5_scaling.py --executed).
    overlap: bool = False
    # Negative-curvature policy (core.hf NC_MODES): "truncate" (passive
    # φ-best competition at the solution's norm scale) | "escape"
    # (saddle-free |λ_min|-scaled escape step along the NC direction,
    # Arjovsky arXiv:1506.00059 — λ from KrylovResult.nc_lambda).
    nc_mode: str = "truncate"
    # Divergence sentinel (core.hf): reject_nonfinite rolls back any outer
    # step whose accepted loss or update is non-finite (NaN curvature
    # batch, overflow) and boosts λ instead of poisoning the params;
    # strict_descent additionally rejects finite steps whose loss rises by
    # more than descent_guard·max(1, |f0|). reject_boost scales λ on a
    # rejection (<=0 → damping_inc²).
    reject_nonfinite: bool = True
    strict_descent: bool = False
    descent_guard: float = 0.0
    reject_boost: float = 0.0


@dataclasses.dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    shape: InputShape
    opt: HFOptConfig = HFOptConfig()
    seed: int = 0
    steps: int = 100
    fsdp: bool = False                         # shard stacked params over data axis too
    remat: bool = False                        # activation checkpointing on blocks
    use_flash_attention: bool = False          # Pallas kernel path (TPU)
