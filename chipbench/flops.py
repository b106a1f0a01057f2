"""Operations and bytes the algorithm needs, in closed form from shapes.

Attention follows the reference's convention (its benchmarks/benchmark.py,
BASELINE.md): forward = 4*b*s^2*h*d, halved when causal; backward = 2.5 x
forward, which COUNTS the backward's recomputation of the scores; forward +
backward = 3.5 x.  A kernel's roofline share uses that count.  A model's
FLOP utilization does not count recomputation: backward = 2 x forward there,
and remat's second forward is not counted either.
"""


def attention_fwd_flops(batch, seq, heads, d_head, causal=True):
    return 4.0 * batch * seq * seq * heads * d_head / (2 if causal else 1)


def attention_kernel_flops(batch, seq, heads, d_head, causal=True):
    """Forward + backward of one attention call, the reference's 3.5 x."""
    return 3.5 * attention_fwd_flops(batch, seq, heads, d_head, causal)


def attention_kernel_bytes(batch, seq, heads, kv_heads, d_head, itemsize=2):
    """Least HBM traffic of forward + backward: the forward reads q, k, v and
    writes o; the backward reads q, k, v, o, do and writes dq, dk, dv."""
    q_like = batch * seq * heads * d_head * itemsize
    kv_like = batch * seq * kv_heads * d_head * itemsize
    return 6 * q_like + 6 * kv_like


def roofline_share(flops, nbytes, seconds, peaks):
    """(share of the binding roof in percent, which roof binds)."""
    t_compute = flops / peaks["bf16_flops_s"]
    t_memory = nbytes / peaks["hbm_bytes_s"]
    bound = "compute" if t_compute >= t_memory else "memory"
    return 100.0 * max(t_compute, t_memory) / seconds, bound


def matmul_params(model):
    """Parameters that are multiplied per token: the projections, the MLP
    and the output head.  The embedding table is a lookup and the norm
    scales are elementwise."""
    d, hd = model["hidden_size"], model["head_dim"]
    nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    layer = (d * nq * hd + 2 * d * nkv * hd + nq * hd * d
             + 3 * d * model["intermediate_size"])
    return model["num_hidden_layers"] * layer + model["vocab_size"] * d


def model_flops_per_token(model, seq):
    """6 x matmul parameters + 3 x causal attention forward, per token."""
    attn = (model["num_hidden_layers"] * attention_fwd_flops(
        1, seq, model["num_attention_heads"], model["head_dim"])) / seq
    return 6.0 * matmul_params(model) + 3.0 * attn


def model_flops_util(model, seq, tokens_per_s_chip, peaks):
    return (100.0 * model_flops_per_token(model, seq) * tokens_per_s_chip
            / peaks["bf16_flops_s"])
