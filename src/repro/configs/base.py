"""ModelConfig: the single dataclass every architecture instantiates.

One ``src/repro/configs/<arch>.py`` per assigned architecture exports
``CONFIG`` (the exact published config) and ``reduced()`` (a same-family
shrunken config for CPU smoke tests).
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | hybrid | ssm | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    # --- attention ---
    rope_theta: float = 1e4
    qkv_bias: bool = False
    m_rope: bool = False
    mrope_sections: tuple[int, int, int] = (16, 24, 24)
    sliding_window: int | None = None    # window size for local layers
    global_every: int = 0                # gemma3: layer i is global iff
    #                                      (i+1) % global_every == 0; 0 = all global
    attn_logit_softcap: float | None = None
    pos_encoding: str = "rope"           # rope | none (NoPE: Jamba's
    #                                      attention has no positions)

    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    moe_every: int = 1                   # MoE FFN at layers i % moe_every == moe_offset
    moe_offset: int = 0
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    moe_renormalize: bool = True         # top-k gates rescaled to sum to 1
    #                                      (Jamba keeps the softmax values)
    experts_held: int = 0                # experts whose weights this chip
    #                                      holds (0 = all n_experts); the
    #                                      router stays n_experts wide
    experts_offset: int = 0              # id of the first held expert

    # --- hybrid (Jamba): attention at layers i % attn_every == attn_offset,
    #     Mamba elsewhere.  attn_every == 0 means every layer is attention.
    attn_every: int = 0
    attn_offset: int = 4
    mamba_d_state: int = 16
    mamba_expand: int = 2
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 0               # 0 -> ceil(d_model/16)
    mamba_chunk: int = 128
    mamba_inner_norms: bool = False      # Jamba: RMSNorm over dt, B and C
    #                                      after x_proj

    # --- RWKV-6 ---
    rwkv: bool = False
    rwkv_head_dim: int = 64
    rwkv_decay_lora: int = 64
    rwkv_chunk: int = 128

    # --- encoder-decoder (Whisper backbone; conv frontend stubbed) ---
    encoder_layers: int = 0              # 0 = decoder-only

    # --- anytime nesting (the paper's technique as a config knob) ---
    nest_levels: int = 1                 # width nesting; 1 = off
    depth_nest_levels: int = 1

    # --- numerics / execution ---
    dtype: str = "bfloat16"
    norm_eps: float = 1e-6
    norm_kind: str = "rmsnorm"           # rmsnorm | layernorm
    tie_embeddings: bool = False
    attn_chunk: int = 1024               # query-chunk for ref attention
    attn_backend: str = "ref"            # ref | kernel
    remat: bool = True
    loss_chunk: int = 0                  # 0 = unchunked cross-entropy
    unroll_layers: bool = False          # True: no layer scan (flop calib)
    # --- hillclimb levers (EXPERIMENTS.md §Perf) ---
    remat_policy: str = "full"           # full | save_dots
    window_banded: bool = False          # sliding-window attn reads only
    #                                      the key band, not the full seq
    prefill_last_only: bool = False      # prefill emits last-position
    #                                      logits only (serving semantics)
    nest_backend: str = "blocks"         # blocks | masked (paper-faithful
    #                                      dense-masked infra baseline)
    attn_unroll_chunks: bool = False     # python-loop the attn chunk map
    #                                      (flop-calibration: no while op)
    moe_dispatch: str = "onehot"         # onehot (GShard) | gather (sorted
    #                                      index dispatch — §Perf cell D) |
    #                                      dropless (grouped matmuls over
    #                                      the held experts, no capacity)

    def __post_init__(self):
        if self.family not in ("dense", "moe", "hybrid", "ssm", "encdec",
                               "vlm"):
            raise ValueError(f"unknown family {self.family!r}")
        if self.n_experts and not self.top_k:
            raise ValueError("MoE config needs top_k")
        if self.rwkv and self.d_model % self.rwkv_head_dim:
            raise ValueError("d_model must divide into rwkv heads")
        if self.pos_encoding not in ("rope", "none"):
            raise ValueError(f"unknown pos_encoding {self.pos_encoding!r}")
        if self.n_experts:
            held = self.n_experts_here
            if not (0 < held and 0 <= self.experts_offset
                    and self.experts_offset + held <= self.n_experts):
                raise ValueError("held experts must lie within n_experts")
            if held < self.n_experts and self.moe_dispatch != "dropless":
                raise ValueError("a share of the experts needs "
                                 "moe_dispatch='dropless'")

    # ------------------------------------------------------------------ #
    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @property
    def mamba_dt_rank_actual(self) -> int:
        return self.mamba_dt_rank or -(-self.d_model // 16)

    @property
    def n_experts_here(self) -> int:
        """Experts whose weights this chip holds."""
        return self.experts_held or self.n_experts

    @property
    def rwkv_n_heads(self) -> int:
        return self.d_model // self.rwkv_head_dim

    def mixer_kind(self, layer: int) -> str:
        """Which sequence mixer layer ``layer`` (0-based) uses."""
        if self.rwkv:
            return "rwkv"
        if self.attn_every:
            if layer % self.attn_every == self.attn_offset % self.attn_every:
                return "attn"
            return "mamba"
        if self.global_every:
            return "attn" if (layer + 1) % self.global_every == 0 \
                else "attn_local"
        return "attn"

    def ffn_kind(self, layer: int) -> str:
        if self.n_experts and layer % self.moe_every == self.moe_offset:
            return "moe"
        return "dense"

    def layer_plan(self) -> list[tuple[str, str]]:
        return [(self.mixer_kind(i), self.ffn_kind(i))
                for i in range(self.n_layers)]

    def layer_period(self) -> int:
        """Smallest repeating period of the layer plan (for scan grouping)."""
        plan = self.layer_plan()
        for p in range(1, self.n_layers + 1):
            if all(plan[i] == plan[i % p] for i in range(self.n_layers)):
                return p
        return self.n_layers

    def param_count(self) -> int:
        """Analytic parameter count (embedding included once)."""
        d, hd = self.d_model, self.head_dim
        total = self.vocab * d                          # embed
        if not self.tie_embeddings:
            total += d * self.vocab                     # unembed
        total += d                                      # final norm
        for mixer, ffn in self.layer_plan():
            total += 2 * d                              # two pre-norms
            if mixer in ("attn", "attn_local"):
                total += d * self.n_heads * hd          # wq
                total += 2 * d * self.n_kv_heads * hd   # wk, wv
                total += self.n_heads * hd * d          # wo
                if self.qkv_bias:
                    total += (self.n_heads + 2 * self.n_kv_heads) * hd
            elif mixer == "mamba":
                di, ds = self.mamba_d_inner, self.mamba_d_state
                dt = self.mamba_dt_rank_actual
                total += d * 2 * di + self.mamba_d_conv * di \
                    + di * (dt + 2 * ds) + dt * di + di * ds + 2 * di \
                    + di * d
                if self.mamba_inner_norms:
                    total += dt + 2 * ds                # dt, B, C norms
            elif mixer == "rwkv":
                total += 5 * d                          # token-shift mus
                total += 4 * d * d + d * d              # r,k,v,g + out
                total += 2 * d * self.rwkv_decay_lora   # decay lora
                total += d                              # u bonus
                total += 2 * d                          # ln_x
            if ffn == "dense":
                total += 3 * d * self.d_ff
            else:
                total += d * self.n_experts             # router
                total += self.n_experts_here * 3 * d * self.d_ff
        if self.encoder_layers:
            # encoder self-attn + ffn, and decoder cross-attn add-ons
            enc = self.encoder_layers * (
                2 * d + d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
                + self.n_heads * hd * d + 3 * d * self.d_ff)
            cross = self.n_layers * (
                d + d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
                + self.n_heads * hd * d)
            total += enc + cross + d                    # + encoder final norm
        return total

    def active_param_count(self) -> int:
        """MoE: params touched per token (top_k experts)."""
        if not self.n_experts:
            return self.param_count()
        d = self.d_model
        total = self.param_count()
        for _, ffn in self.layer_plan():
            if ffn == "moe":
                total -= (self.n_experts_here - self.top_k) * 3 * d \
                    * self.d_ff
        return total

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)
