"""Operations and bytes the algorithm needs, in closed form from shapes.

Attention follows the reference's convention (its benchmarks/benchmark.py,
BASELINE.md): forward = 4*b*s^2*h*d, halved when causal; backward = 2.5 x
forward, which COUNTS the backward's recomputation of the scores; forward +
backward = 3.5 x.  A kernel's roofline share uses that count.  A model's
FLOP utilization does not count recomputation: backward = 2 x forward there,
and remat's second forward is not counted either.

One count serves every configuration: `attention_calls` walks its layers and
reads each one's mask (causal, a sliding window from `layer_types`, or the
block-diffusion mask), the two head widths and the heads.
"""

from collections import Counter

KERNEL_PASSES = 3.5  # forward + backward over forward, recomputation counted


def attention_pairs(model, seq):
    """Visible (query, key) pairs of one sequence in each layer's attention
    call, one entry a layer (one call where the configuration has no layers:
    the op's).  Block diffusion (`block_length` B): a layer runs on a stream
    of 2 x seq and sees seq^2 + seq * B pairs, exactly.  Else a layer that
    `layer_types` (the Hugging Face key) names "sliding_attention" sees, with
    w = min(sliding_window, seq), seq * w - w^2 / 2, and any other causal
    layer seq^2 / 2: the reference's /2, which leaves out the half of the
    diagonal (w / 2 pairs) that a dense mask holds."""
    layers = model.get("num_hidden_layers", 1)
    if "block_length" in model:
        return [seq * seq + seq * model["block_length"]] * layers
    kinds = model.get("layer_types", ["full_attention"] * layers)
    if len(kinds) != layers:
        raise ValueError(f"layer_types names {len(kinds)} layers, "
                         f"num_hidden_layers is {layers}")
    w = min(model.get("sliding_window") or seq, seq)
    return [seq * w - w * w / 2 if kind == "sliding_attention"
            else seq * seq / 2 for kind in kinds]


def attention_calls(model, mix):
    """One step's attention calls grouped by their mask, in layer order:
    [(layers, forward FLOPs of one call, least HBM bytes of one call's
    forward + backward)].  q and k are `qk_head_dim` wide, v and the output
    `v_head_dim`, both `head_dim` where the configuration states no two
    widths.  A visible pair costs 2 d_qk FLOPs for its score and 2 d_v for
    its share of the output, a query head.  Bytes, a token of the call's
    stream: the query side reads q twice and writes dq at d_qk,
    writes o and reads it again with do at d_v, a query head; the KV side
    reads k twice and writes dk at d_qk, reads v twice and writes dv at d_v,
    a KV head, each element 2 bytes (bf16).  Where d_qk == d_v that is 6
    q-shaped + 6 kv-shaped tensors."""
    d_qk = model.get("qk_head_dim", model.get("head_dim"))
    d_v = model.get("v_head_dim", model.get("head_dim"))
    heads = model["num_attention_heads"]
    kv_heads = model["num_key_value_heads"]
    stream = 2 * mix["seq"] if "block_length" in model else mix["seq"]
    nbytes = (mix["batch"] * stream * (heads + kv_heads)
              * (3 * d_qk + 3 * d_v) * 2)
    groups = Counter(attention_pairs(model, mix["seq"]))
    return [(layers, (2 * d_qk + 2 * d_v) * mix["batch"] * pairs * heads,
             nbytes) for pairs, layers in groups.items()]


def attention_fwd_flops(model, mix):
    """Forward FLOPs of one step's attention calls, every layer."""
    return sum(layers * flop for layers, flop, _ in attention_calls(model,
                                                                    mix))


def roofline_share(flops, nbytes, seconds, peaks):
    """(share of the binding roof in percent, which roof binds)."""
    t_compute = flops / peaks["bf16_flops_s"]
    t_memory = nbytes / peaks["hbm_bytes_s"]
    bound = "compute" if t_compute >= t_memory else "memory"
    return 100.0 * max(t_compute, t_memory) / seconds, bound


def share_of_peak(flop, seconds, peaks):
    """`flop` FLOPs in `seconds` as a percentage of the bf16 peak."""
    return 100.0 * flop / seconds / peaks["bf16_flops_s"]


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
    """6 x matmul parameters + 3 x the attention forward, per token."""
    attn = attention_fwd_flops(model, {"batch": 1, "seq": seq}) / seq
    return 6.0 * matmul_params(model) + 3.0 * attn


def model_flops_util(model, seq, tokens_per_s_chip, peaks):
    return (100.0 * model_flops_per_token(model, seq) * tokens_per_s_chip
            / peaks["bf16_flops_s"])
