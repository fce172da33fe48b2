"""Work a DeepSeek-V3-layout configuration (latent attention, routed and
shared experts) needs on one chip's share of it, counted from its shapes.

The yardstick of the MoE training cell's ``train.mfu`` and
``moe.gmm_roofline``: the operations the algorithm needs, not what the
code happens to run. Routed experts count at their expectation on this
chip, ``num_experts_per_tok x held / router_experts`` rows per token;
recomputation is not counted.
"""

from __future__ import annotations


def mla_matmul_params(model: dict) -> int:
    """Matrix parameters of one latent-attention block without a query
    latent: q, the kv latent with its rope key, the latent's keys and
    values, and the output."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    vd, kv = model["v_head_dim"], model["kv_lora_rank"]
    return (d * h * (nope + rope) + d * (kv + rope)
            + kv * h * (nope + vd) + h * vd * d)


def moe_matmul_params_per_token(model: dict) -> float:
    """Matrix parameters one token meets in one MoE layer's MLP: the
    router, the shared experts and its expected share of held routed
    experts."""
    d, ff = model["hidden_size"], model["moe_intermediate_size"]
    held = model["held_experts"][1]
    routed = model["num_experts_per_tok"] * held / model["router_experts"]
    return (d * model["router_experts"]
            + 3 * d * ff * model["n_shared_experts"]
            + routed * 3 * d * ff)


def train_flops_per_token(model: dict, seq: int) -> float:
    """Operations a training step needs per token on this chip: forward
    and backward (three times the forward) of every matrix multiplication
    (attention projections, the dense layers' MLP, the MoE layers' router,
    shared and expected routed experts, the head over the vocabulary
    held), plus causal attention's scores and weighted sum over the
    (seq + 1) / 2 keys a token sees on average."""
    layers = model["num_hidden_layers"]
    dense = model["first_k_dense_replace"]
    d = model["hidden_size"]
    mm = (layers * mla_matmul_params(model)
          + dense * 3 * d * model["intermediate_size"]
          + (layers - dense) * moe_matmul_params_per_token(model)
          + model["vocab_size"] * d)
    per_key = model["num_attention_heads"] * (
        model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
        + model["v_head_dim"])
    attn = layers * 2 * per_key * (seq + 1) / 2
    return 3 * (2 * mm + attn)


def gmm_flops_per_row(model: dict) -> float:
    """Operations of one routed row in the held experts' grouped matmuls,
    forward and backward: three SwiGLU matmuls of d x ff, each 2 d ff
    forward and twice that backward."""
    return 18 * model["hidden_size"] * model["moe_intermediate_size"]
