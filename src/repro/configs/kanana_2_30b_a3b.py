"""kanana-2-30b-a3b [moe] — 48L d_model=2048 32H MLA without a query
latent (q 2048 -> 32x192; kv latent 512 with its RMSNorm; per head 128
no-rope + a shared 64 rope key; v 128; softmax scale 1/sqrt(192)), rope
theta 1e6 over adjacent channel pairs (``rope_interleave``), vocab 128256,
untied head. Layer 0 has a dense SwiGLU MLP (6144); layers 1-47 are MoE:
128 routed SwiGLU experts of 768, top-6, plus 2 shared (one MLP of 1536).
Routing is DeepSeek-V3's ``noaux_tc`` gate with n_group = topk_group = 1:
sigmoid scores in f32, top-6 chosen on score + e_score_correction_bias,
weights the unbiased scores of those 6 renormalised and x 2.448.
[hf:kakaocorp/kanana-2-30b-a3b-instruct-2601,
https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/main/config.json]

The expert layer holds all 128 experts here (``held=(0, 128)``), dropless;
an expert-parallel shard holds a range of them. Attention runs in query
blocks of 512 (``_mla_attend_chunked``), which keeps a backward pass at
8k positions to one block's scores."""

from repro.models import LayerSpec, MLASpec, ModelConfig, MoESpec

_LAYOUT = (LayerSpec(kind="mla", mlp="dense"),) + tuple(
    LayerSpec(kind="mla", mlp="moe") for _ in range(47))

CONFIG = ModelConfig(
    name="kanana-2-30b-a3b", family="moe",
    n_layers=48, d_model=2048, n_heads=32, n_kv_heads=32,
    d_ff=6144, vocab=128256,
    layout=_LAYOUT,
    mla=MLASpec(q_lora_rank=None, kv_lora_rank=512,
                qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
    moe=MoESpec(num_experts=128, top_k=6, expert_d_ff=768,
                num_shared_experts=2, shared_d_ff=768,
                score_func="sigmoid", norm_topk=True, routed_scale=2.448,
                select_bias=True, router_f32=True, held=(0, 128)),
    rope_theta=1e6, tie_embeddings=False,
    act="swiglu", norm="rms", pos="rope",
    attn_impl="chunked", attn_block=512,
    subquadratic=False,  # MLA is full attention
)

REDUCED = ModelConfig(
    name="kanana-2-reduced", family="moe",
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
    d_ff=128, vocab=97,
    layout=(LayerSpec(kind="mla", mlp="dense"),
            LayerSpec(kind="mla", mlp="moe")),
    mla=MLASpec(q_lora_rank=None, kv_lora_rank=16,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16),
    moe=MoESpec(num_experts=8, top_k=3, expert_d_ff=32,
                num_shared_experts=2, shared_d_ff=32,
                score_func="sigmoid", norm_topk=True, routed_scale=2.448,
                select_bias=True, router_f32=True, held=(0, 8)),
    rope_theta=1e6, tie_embeddings=False,
    act="swiglu", norm="rms", pos="rope",
    attn_impl="chunked", attn_block=8,
    subquadratic=False, dtype="float32",
)
