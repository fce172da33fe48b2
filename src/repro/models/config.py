"""Model configuration schema + layer-layout machinery.

A ``ModelConfig`` fully describes one architecture from the assigned pool
(dense / GQA / MLA / MoE / SSM / hybrid / VLM / audio backbones). The
per-layer structure is a list of ``LayerSpec``; ``layout_groups`` factors
it into scan-able groups (smallest repeating super-block, else runs of
identical specs) so the compiled HLO stays small for 46-60 layer stacks —
essential for the 512-device dry-run compile times.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple


@dataclass(frozen=True)
class MoESpec:
    """Routed experts as the published gate defines them. ``num_experts``
    is the router's width; ``held`` says which of them this layer holds,
    ``(first, count)``: the layer computes only their part of the result,
    with no capacity and no dropped token. ``held=None`` holds every
    expert behind the capacity-bounded dispatch (``capacity_factor``)."""
    num_experts: int
    top_k: int
    expert_d_ff: int
    num_shared_experts: int = 0
    shared_d_ff: int = 0          # per shared expert
    router_noise: float = 0.0
    capacity_factor: float = 1.25
    score_func: str = "softmax"   # "softmax" | "sigmoid"
    norm_topk: bool = True        # top-k weights renormalised to sum 1
    routed_scale: float = 1.0     # routed_scaling_factor
    select_bias: bool = False     # init ``bias``, per expert, for selection
    router_f32: bool = False      # router logits at full f32 precision
    held: Optional[Tuple[int, int]] = None

    def held_count(self) -> int:
        return self.num_experts if self.held is None else self.held[1]


@dataclass(frozen=True)
class SSMSpec:
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    chunk: int = 256              # SSD chunk length


@dataclass(frozen=True)
class MLASpec:
    """DeepSeek-V2 multi-head latent attention dims; ``q_lora_rank=None``
    projects queries directly, with no query latent."""
    q_lora_rank: Optional[int] = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class LayerSpec:
    """One decoder block: attention (or SSM) + MLP (dense or MoE)."""
    kind: str = "attn"            # "attn" | "mla" | "ssm"
    window: Optional[int] = None  # sliding-window size (None = full/global)
    mlp: str = "dense"            # "dense" | "moe"


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # "dense"|"moe"|"ssm"|"hybrid"|"vlm"|"audio"
    n_layers: int
    d_model: int
    n_heads: int                  # 0 for attn-free
    n_kv_heads: int
    d_ff: int                     # dense-MLP hidden size (0 if none)
    vocab: int
    head_dim: Optional[int] = None           # default d_model // n_heads
    layout: Tuple[LayerSpec, ...] = ()
    moe: Optional[MoESpec] = None
    ssm: Optional[SSMSpec] = None
    mla: Optional[MLASpec] = None
    # attention details
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    rotary_pct: float = 1.0       # partial rotary (stablelm: 0.25)
    attn_softcap: Optional[float] = None      # gemma2: 50.0
    final_softcap: Optional[float] = None     # gemma2: 30.0
    query_scale: Optional[float] = None       # gemma2: 1/sqrt(query_pre_attn)
    # block details
    norm: str = "rms"             # "rms" | "ln"
    act: str = "swiglu"           # "swiglu" | "geglu" | "gelu"
    post_norms: bool = False      # gemma2 post-attn/post-ffn norms
    pos: str = "rope"             # "rope" | "sinusoidal" | "none"
    scale_embed: bool = False     # gemma2: embed * sqrt(d_model)
    tie_embeddings: bool = False
    # modality frontend (STUB): inputs arrive as precomputed embeddings
    input_mode: str = "tokens"    # "tokens" | "embeds" | "tokens+prefix"
    prefix_len: int = 0           # vlm: number of patch-embedding positions
    # attention execution path: "naive" materializes [s,s] scores (XLA
    # default); "chunked" is the trace-time flash build (tile-skipped,
    # online softmax) — the XLA twin of kernels/flash_attention
    attn_impl: str = "naive"
    attn_block: int = 2048
    # MoE execution path: "global" single dispatch (mesh-free reference);
    # "local" shard_map per-shard dispatch (EP all-to-all / TP psum)
    moe_impl: str = "global"
    # numerics
    dtype: str = "bfloat16"
    # long-context capability: True iff decode state is o(seq_len)
    subquadratic: bool = False

    def resolved_head_dim(self) -> int:
        if self.head_dim is not None:
            return self.head_dim
        assert self.n_heads > 0
        return self.d_model // self.n_heads

    def default_layout(self) -> Tuple[LayerSpec, ...]:
        if self.layout:
            return self.layout
        return tuple(LayerSpec() for _ in range(self.n_layers))

    # -- parameter count (for 6·N·D roofline bookkeeping) ---------------------
    def param_counts(self) -> Tuple[int, int]:
        """(total_params, active_params_per_token)."""
        d, hd = self.d_model, (self.resolved_head_dim() if self.n_heads else 0)
        # active counts the LM-head matmul once; the token-embedding gather
        # is not a matmul (0 FLOPs), so it never enters MODEL_FLOPS
        total = self.vocab * d
        active = self.vocab * d
        if not self.tie_embeddings:
            total += self.vocab * d
        for spec in self.default_layout():
            t = a = 0
            if spec.kind == "attn":
                q = d * self.n_heads * hd + (self.n_heads * hd if self.qkv_bias else 0)
                kv = 2 * (d * self.n_kv_heads * hd + (self.n_kv_heads * hd if self.qkv_bias else 0))
                o = self.n_heads * hd * d
                t = a = q + kv + o
            elif spec.kind == "mla":
                m = self.mla
                qh = m.qk_nope_head_dim + m.qk_rope_head_dim
                q = (d * self.n_heads * qh if m.q_lora_rank is None
                     else d * m.q_lora_rank
                     + m.q_lora_rank * self.n_heads * qh)
                t = a = (q + d * (m.kv_lora_rank + m.qk_rope_head_dim)
                         + m.kv_lora_rank * self.n_heads
                         * (m.qk_nope_head_dim + m.v_head_dim)
                         + self.n_heads * m.v_head_dim * d)
            elif spec.kind == "ssm":
                s = self.ssm
                d_in = s.expand * d
                nh = d_in // s.head_dim
                # in_proj (z,x,B,C,dt) + conv + out_proj + A,D
                t = a = (d * (2 * d_in + 2 * s.n_groups * s.d_state + nh)
                         + s.d_conv * (d_in + 2 * s.n_groups * s.d_state)
                         + d_in * d + 2 * nh)
            if spec.mlp == "none":
                pass
            elif spec.mlp == "dense":
                gates = 2 if self.act in ("swiglu", "geglu") else 1
                t_mlp = (gates + 1) * d * self.d_ff
                t += t_mlp
                a += t_mlp
            elif spec.mlp == "moe":
                m = self.moe
                gates = 2 if self.act in ("swiglu", "geglu") else 1
                per_expert = (gates + 1) * d * m.expert_d_ff
                shared = m.num_shared_experts * (gates + 1) * d * m.shared_d_ff
                router = d * m.num_experts + (m.num_experts
                                              if m.select_bias else 0)
                t += m.held_count() * per_expert + shared + router
                a += m.top_k * per_expert + shared + router
            total += t
            active += a
        return total, active


# ---------------------------------------------------------------------------
# Layout factoring for scan-over-layers
# ---------------------------------------------------------------------------

def layout_groups(layout: Sequence[LayerSpec]) -> List[Tuple[Tuple[LayerSpec, ...], int]]:
    """Factor the layer list into (super_block, repeats) groups.

    Preference order:
      1. smallest period p with layout[i] == layout[i mod p]  → one group,
         super-block of p layers scanned L/p times (gemma2 p=2, jamba p=8);
      2. otherwise runs of identical consecutive specs, each scanned
         (deepseek-v2: [dense]×1 + [moe]×59).

    The compiled HLO contains each distinct super-block body once.
    """
    L = len(layout)
    # p == L is excluded: "repeating once" is no repetition, and accepting
    # it would unroll heterogeneous stacks (e.g. deepseek's 1+59 layout)
    # into one giant super-block.
    for p in range(1, L):
        if L % p != 0:
            continue
        if all(layout[i] == layout[i % p] for i in range(L)):
            return [(tuple(layout[:p]), L // p)]
    # runs fallback
    groups: List[Tuple[Tuple[LayerSpec, ...], int]] = []
    i = 0
    while i < L:
        j = i
        while j < L and layout[j] == layout[i]:
            j += 1
        groups.append(((layout[i],), j - i))
        i = j
    return groups
