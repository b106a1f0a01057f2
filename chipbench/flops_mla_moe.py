"""Operations of the latent-attention MoE cells, in closed form from the
configuration and the traffic (conventions as in flops.py: a kernel's roofline
counts what the kernel has to do, the backward's recomputation of the scores
included; a model's FLOP utilization counts no recomputation).

The attention's heads are two widths wide: q and k `qk_head_dim` (192 = 128
without position + 64 rotary), v and the output `v_head_dim` (128).  A visible
(query, key) pair costs 2 x qk_head_dim FLOPs for its score and 2 x v_head_dim
for its share of the output: the USEFUL count.  A kernel that pads either
width pays for the padding in its time and not here.
"""

# one routed expert's parameters (gate, up, down), the router's, and a share
# of the bf16 peak: the block-diffusion MoE cells' own
from .flops_bd_moe import expert_params, router_params, share_of_peak  # noqa: F401


def attention_fwd_flops(model, mix):
    """Forward FLOPs of ONE layer's causal attention over the batch."""
    pair = 2.0 * model["qk_head_dim"] + 2.0 * model["v_head_dim"]
    return (pair * mix["batch"] * mix["seq"] * mix["seq"] / 2
            * model["num_attention_heads"])


def flash_kernel_flops(model, mix):
    """Forward + backward of every layer's attention call in one step, the
    reference's 3.5 x forward (flops.attention_kernel_flops's convention)."""
    return 3.5 * model["num_hidden_layers"] * attention_fwd_flops(model, mix)


def attention_params(model):
    """Matrix parameters of one layer's latent attention: q at full rank,
    the down-projection (latent + the one rotary key), the up-projection
    (k_nope and v for every head), the output."""
    d, n = model["hidden_size"], model["num_attention_heads"]
    return (d * n * model["qk_head_dim"]
            + d * (model["kv_lora_rank"] + model["qk_rope_head_dim"])
            + model["kv_lora_rank"] * n * (model["qk_nope_head_dim"]
                                           + model["v_head_dim"])
            + n * model["v_head_dim"] * d)


def dense_mlp_params(model):
    return 3 * model["hidden_size"] * model["intermediate_size"]


def shared_params(model):
    """The shared experts: one SwiGLU, n_shared_experts x the expert width."""
    return model["n_shared_experts"] * expert_params(model)


def sparse_layers(model):
    return model["num_hidden_layers"] - model["first_k_dense_replace"]


def token_params(model):
    """Matrix parameters EVERY token meets in one pass: each layer's
    attention, the leading dense layers' MLP, each sparse layer's router and
    shared experts, the output head."""
    return (model["num_hidden_layers"] * attention_params(model)
            + model["first_k_dense_replace"] * dense_mlp_params(model)
            + sparse_layers(model) * (router_params(model)
                                      + shared_params(model))
            + model["vocab_size"] * model["hidden_size"])


def step_model_flops(model, mix, slots_here):
    """Model FLOPs of one step, no recomputation counted: 6 x the matrix
    parameters each token meets (the routed experts on the (token, expert)
    pairs computed here, `slots_here`, all layers) + 3 x the causal
    attention forward."""
    tokens = mix["batch"] * mix["seq"]
    return (6.0 * (token_params(model) * tokens
                   + expert_params(model) * slots_here)
            + 3.0 * model["num_hidden_layers"]
            * attention_fwd_flops(model, mix))
