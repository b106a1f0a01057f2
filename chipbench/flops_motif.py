"""Operations and bytes of the Motif-3 cell, in closed form from the
configuration and the traffic (conventions as in flops.py: a model's FLOP
utilization counts no recomputation).

The attention is grouped differential latent attention: q from a 1,024-wide
latent, k_nope and v for 16 KV heads from a 512-wide latent, 80 query heads
of which 64 are signal heads that the gate, lambda and the output
projection read.  The kernels compute all 80 heads at 192 / 128
(flops.attention_calls counts them so, each layer by its window).  mHC mixes
four streams around each of a layer's two sublayers: its matrix is the
16,384 x 24 projection of each, and its time is its bytes (`mhc_bytes`).
"""

from .flops import attention_fwd_flops
# one routed expert's parameters (gate, up, down) and the router's
from .flops_bd_moe import expert_params, router_params

BYTES = 2  # the streams' dtype, bf16


def signal_heads(model):
    return model["num_attention_heads"] - model["num_noise_heads"]


def attention_params(model):
    """Matrix parameters of one layer's GDLA: q's down- and up-projection,
    the KV down-projection (latent + the one rotary key) and up-projection
    (k_nope and v for every KV head), lambda, the gate and the output."""
    d, n = model["hidden_size"], model["num_attention_heads"]
    signal, v = signal_heads(model), model["v_head_dim"]
    return (d * model["q_lora_rank"]
            + model["q_lora_rank"] * n * model["qk_head_dim"]
            + d * (model["kv_lora_rank"] + model["qk_rope_head_dim"])
            + model["kv_lora_rank"] * model["num_key_value_heads"]
            * (model["qk_nope_head_dim"] + v)
            + d * signal + 2 * d * signal * v)


def mhc_params(model):
    """One layer's mHC projections: two sublayers x (n d) x (2n + n^2)."""
    n = model["mhc_expansion_rate"]
    return 2 * n * model["hidden_size"] * (2 * n + n * n)


def dense_mlp_params(model):
    return 3 * model["hidden_size"] * model["intermediate_size"]


def shared_params(model):
    return model["num_shared_experts"] * expert_params(model)


def dense_layers(model):
    """The held layers before `n_dense_first_layers`."""
    lo, hi = model["layers_held"]
    return max(0, min(hi, model["n_dense_first_layers"]) - lo)


def token_params(model):
    """Matrix parameters EVERY token meets in one pass: each layer's
    attention and mHC, the dense layers' MLP, each sparse layer's router and
    shared expert, the output head."""
    layers, dense = model["num_hidden_layers"], dense_layers(model)
    return (layers * (attention_params(model) + mhc_params(model))
            + dense * dense_mlp_params(model)
            + (layers - dense) * (router_params(model) + shared_params(model))
            + model["vocab_size"] * model["hidden_size"])


def step_model_flops(model, mix, slots_here):
    """Model FLOPs of one step, no recomputation counted: 6 x the matrix
    parameters each token meets (the routed experts on the (token, expert)
    pairs computed here, `slots_here`, all layers) + 3 x the attention
    forward (flops.attention_fwd_flops: each layer by its window)."""
    tokens = mix["batch"] * mix["seq"]
    return (6.0 * (token_params(model) * tokens
                   + expert_params(model) * slots_here)
            + 3.0 * attention_fwd_flops(model, mix))


def mhc_bytes(model, mix):
    """The bytes mHC cannot avoid in one step: a sublayer pass reads the
    streams once and writes them once (2 n d a token), writes the
    sublayer's input and reads its output (2 d), in the streams' dtype; two
    sublayers a layer; the forward, the recomputed forward, and the
    backward at twice the forward: four passes."""
    n, d = model["mhc_expansion_rate"], model["hidden_size"]
    tokens = mix["batch"] * mix["seq"]
    per_pass = (2 * n + 2) * d * BYTES * tokens
    return 4 * 2 * model["num_hidden_layers"] * per_pass


def mhc_least_seconds(model, mix, peaks):
    """mhc_bytes over the chip's bandwidth: what the mixing takes at best."""
    return mhc_bytes(model, mix) / peaks["hbm_bytes_s"]
