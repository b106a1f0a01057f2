"""kanana-2-30b-a3b's block as its `config.json` (`model_type: deepseek_v3`)
describes it, trained on the next token, in plain float32 `jax.numpy`: RMSNorm,
latent attention (q at full rank; one 576-wide down-projection a token whose
512 latent channels are RMS-normed and come up to 32 x (128 + 128), and whose
64 rotary channels are ONE key for all heads; rotary with the interleaved
pairing on the 64 rotary channels of q and on that key; softmax over 192-wide
scores, 128-wide values), a leading dense SwiGLU layer, then sparse layers: a
sigmoid router over all experts whose bias enters the choice and not the gate,
the chosen gates renormalised and scaled, SwiGLU experts, and the shared
experts every token takes.  No kernel, no sorting, no grouped product, no
remat, no sharding; independent of `burst_attn_tpu`'s model code.

With `h = rmsnorm(x) * scale` (eps from the config), N heads:
    q = h Wq                      -> [S, N, 192] = [q_nope 128 | q_rope 64]
    [c | k_rope] = h Wkv_a        -> [S, 512 + 64];  c = rmsnorm(c) * scale_c
    [k_nope | v] = c Wkv_b        -> [S, N, 128 + 128]
    q = [q_nope | rope(q_rope)],  k = [k_nope | rope(k_rope) for every head]
    x += softmax(q k^T / sqrt(192) + causal) v Wo
  layer 0:  x += (silu(h' Wg) * (h' Wu)) Wd                       (width 6144)
  layers 1..:
    s = sigmoid(h' Wr);  S = the k largest of s + b;  g_e = s_e / (sum_S s +
    1e-20) * 2.448 for e in S (WITHOUT b)
    x += sum over e in S that are HELD of g_e Expert_e(h') + Shared(h')
rope rotates channels (2i, 2i+1) together by position * theta^(-2i/64).
The loss is the mean next-token cross entropy over the labelled positions.

Departures from the published code, each because the deployment's cut says so
(configs/kanana2_30b_a3b_ep8_d8.json): only the experts `held` here add to a
sparse layer's output (what the absent ones would add is left out, as in the
program, and the partial sum goes on); the shared experts are whole; the
vocabulary is the slice held.  The bias `b` is a fixed leaf (`router_bias`,
no update rule: the rule is a training recipe, not in `config.json`) and no
auxiliary loss is added.  `n_group` = `topk_group` = 1: the group limit is
trivial and not written.

Attention runs a head at a time (`lax.map` over heads, each head's body
rematerialised in the backward) so that 4,096 tokens fit beside the trainer's
state: one head's [S, S] scores, not thirty-two.  The loop over the held
experts is a `lax.scan` over their stacked weights (every expert sees every
token, weighted by its gate there, 0 where not chosen): one body compiled, not
one an expert (references/bd_moe_lm.py has the compile times).

Parameters are the trainer's own pytree (the weights under test, cast up):
embed [V, D], layers[i] {attn_norm, wq [D, N, 192], wkv_a [D, 576], kv_norm
[512], wkv_b [512, N, 256], wo [N, 128, D], mlp_norm, and either w_gate, w_up
[D, F], w_down [F, D] (dense) or router [D, E], router_bias [E], w_gate, w_up
[E_held, D, F], w_down [E_held, F, D], shared_gate, shared_up [D, Fs],
shared_down [Fs, D]}, final_norm, lm_head [V, D].
"""

import jax
import jax.numpy as jnp

# Bounds of the system (bf16 weights and activations, float32 router and
# accumulation) against this float32 model at 4,096 tokens, published widths,
# seeded weights, the routers' biases balanced as a cell's run balances them
# (runners/train_mla_moe.balance_biases, on the whole 16,384-token batch the
# 4,096 are the head of).  Each but the loss's lies between two readings
# taken AT THAT STATE on the chip (PERF.md section 6, PR 34): the largest the
# system gave over twelve runs of twelve seeds, and what this reference gives
# against itself with every activation rounded through an 8-bit float
# (`round_to` float8_e4m3fn: 3 bits of mantissa where bf16 keeps 8) on two
# seeds (`python3 -m chipbench.runners.train_mla_moe --seed N`), which has to
# fail, by one of the limits and not by each.
#   logits_rel_rms, relative RMS error of the logits: system 3.9e-2 to
#     4.3e-2, 8-bit 0.128 and 0.133.  The limit has 1.7 times of room on
#     either side and no more: the two readings lie 3.0 apart, so this
#     number alone would not hold the system against the 8-bit path; the
#     flips and the gradient do (next).  Six times bd_moe_lm.py's reading: a
#     gate here is 2.448 / 6 of a token's routed sum where it is an eighth
#     there, and balanced biases make the sixth choice a closer call.
#   routing_flips_share, of the (token, sparse layer) pairs the share whose
#     chosen SET of experts differs from the reference's: system 11.7 % to
#     12.7 %, 8-bit 59.0 % and 59.6 % (1.9 times of room above, 2.5 below).
#     Rounding moves a token's sixth choice where the sixth and seventh of
#     s + b are close, and a balanced bias is one that makes them close.  A
#     bias in the GATE, a missing scale or a shared expert counted per share
#     moves the logits by far more than rounding does (the CPU tests hold
#     each to 2e-4).
#   loss_abs: the precision hardly moves the loss (system 6e-6 to 1.0e-3 of a
#     loss near 10.0; 8-bit 1.8e-3 and 1.9e-3), so no limit on it separates
#     the two and it takes the accepted trainer cells' limit
#     (decoder_lm.TOL_LOSS_ABS), 10 times the largest seen: it catches a
#     wrong objective, not a precision.
#   grad_rel_max: of the LAST layer's gradient, the largest relative error
#     |g - g_ref| / |g_ref| of a leaf that is not behind the routing: the
#     attention's (attn_norm, wq, wkv_a, kv_norm, wkv_b, wo: the flash
#     kernels' dq, dk at 192 and dv at 128 behind them).  System 2.5e-2 to
#     3.5e-2 (wq: the backward's bf16 scores; the other five under 1e-2);
#     8-bit (activations rounded forward, identity backward) 0.116 and 0.146
#     (wq; the other five 0.058 to 0.070): 1.9 times of room above, 1.8
#     below.  The leaves BEHIND the routing (ROUTED_LEAVES) are reported
#     beside it (`grad_rel_max_routed`) and not bounded, as in bd_moe_lm.py:
#     a flipped sixth choice swaps a whole term of their gradient (0.21 to
#     0.44 on the chip; 0.53 and 0.60 in 8 bits).  `router_bias` has no
#     gradient on either side (it enters a choice) and is left out.
TOL_LOGITS_REL_RMS = 0.075
TOL_ROUTING_FLIPS_SHARE = 0.24
TOL_LOSS_ABS = 1e-2
TOL_GRAD_REL_MAX = 0.065
ROUTED_LEAVES = ("router", "mlp_norm", "w_gate", "w_up", "w_down",
                 "shared_gate", "shared_up", "shared_down")
STATE_LEAVES = ("router_bias",)


def _f32(a):
    return a.astype(jnp.float32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rope_interleaved(x, positions, theta):
    """x [..., S, H] with positions [S]: channels (2i, 2i+1) are the real and
    imaginary part of one number, times exp(i * pos * theta^(-2i/H))."""
    h = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, h, 2, dtype=jnp.float32) / h)
    angle = positions.astype(jnp.float32)[:, None] * freqs      # [S, H/2]
    re, im = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    out = jnp.stack([re * cos - im * sin, re * sin + im * cos], axis=-1)
    return out.reshape(x.shape)


def _attention(q, k, v):
    """Causal softmax attention a head at a time: q, k [N, S, 192], v [N, S,
    128] -> [N, S, 128]."""
    s_len = q.shape[1]
    causal = jnp.tril(jnp.ones((s_len, s_len), bool))
    scale = q.shape[-1] ** -0.5

    @jax.checkpoint
    def head(qkv):
        q_h, k_h, v_h = qkv
        scores = jnp.where(causal, (q_h @ k_h.T) * scale, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v_h

    return jax.lax.map(head, (q, k, v))


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def _experts(h, p, *, held, top_k, gate_scale):
    """(the held chosen experts' part + the shared experts, the chosen sets
    [S, k]) of one sparse layer on h [S, D]."""
    s = jax.nn.sigmoid(h @ _f32(p["router"]))
    _, choice = jax.lax.top_k(s + p["router_bias"], top_k)
    gates = jnp.take_along_axis(s, choice, axis=-1)
    gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    gates = gates * gate_scale

    def add_expert(y, expert):
        e, w_gate, w_up, w_down = expert
        g_e = jnp.sum(jnp.where(choice == e, gates, 0.0), axis=-1)
        return y + g_e[:, None] * _swiglu(h, _f32(w_gate), _f32(w_up),
                                          _f32(w_down)), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                        (jnp.arange(*held), p["w_gate"], p["w_up"],
                         p["w_down"]))
    shared = _swiglu(h, _f32(p["shared_gate"]), _f32(p["shared_up"]),
                     _f32(p["shared_down"]))
    return y + shared, choice


def forward(params, tokens, *, held, top_k, gate_scale, qk_nope, kv_latent,
            rope_theta, rms_norm_eps, round_to=None):
    """tokens [S] (one sequence, positions 0..S-1) -> (float32 logits [S, V],
    the sparse layers' chosen expert sets [sparse layers, S, k]).
    `round_to`: a dtype every activation is rounded through (the
    lower-precision reading the bounds above are set against); None computes
    in float32."""
    # rounded values forward, the identity backward (bd_moe_lm.py)
    rnd = (lambda a: a) if round_to is None else (
        lambda a: a + jax.lax.stop_gradient(
            a.astype(round_to).astype(jnp.float32) - a))
    positions = jnp.arange(tokens.shape[0])
    n_heads = params["layers"][0]["wq"].shape[1]

    def layer(x, p):
        h = rnd(_rms_norm(x, p["attn_norm"], rms_norm_eps))
        q = jnp.einsum("sd,dnh->nsh", h, _f32(p["wq"]))
        down = h @ _f32(p["wkv_a"])
        latent = rnd(_rms_norm(down[:, :kv_latent], p["kv_norm"],
                               rms_norm_eps))
        up = jnp.einsum("sc,cnh->nsh", latent, _f32(p["wkv_b"]))
        k_rope = _rope_interleaved(down[:, kv_latent:], positions, rope_theta)
        q = rnd(jnp.concatenate(
            [q[..., :qk_nope],
             _rope_interleaved(q[..., qk_nope:], positions, rope_theta)],
            axis=-1))
        k = rnd(jnp.concatenate(
            [up[..., :qk_nope],
             jnp.broadcast_to(k_rope, (n_heads, *k_rope.shape))], axis=-1))
        v = rnd(up[..., qk_nope:])
        o = rnd(_attention(q, k, v))
        x = rnd(x + jnp.einsum("nsh,nhd->sd", o, _f32(p["wo"])))
        h = rnd(_rms_norm(x, p["mlp_norm"], rms_norm_eps))
        if "router" in p:
            y, choice = _experts(h, p, held=held, top_k=top_k,
                                 gate_scale=gate_scale)
        else:
            y, choice = _swiglu(h, _f32(p["w_gate"]), _f32(p["w_up"]),
                                _f32(p["w_down"])), None
        return rnd(x + rnd(y)), choice

    x = rnd(_f32(params["embed"])[tokens])
    chosen = []
    for p in params["layers"]:
        x, choice = layer(x, p)
        if choice is not None:
            chosen.append(choice)
    x = rnd(_rms_norm(x, params["final_norm"], rms_norm_eps))
    return x @ _f32(params["lm_head"]).T, jnp.stack(chosen)


def loss(logits, labels):
    """Mean cross entropy of `labels` [S] over the positions where they are
    not negative."""
    valid = labels >= 0
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[:, None],
                               axis=-1)[:, 0]
    return jnp.sum(jnp.where(valid, nll, 0.0)) / jnp.maximum(
        jnp.sum(valid), 1)


def reference(params, tokens, labels, grads_of=None, **model):
    """{logits [1, S, V], loss, chosen [sparse layers, 1, S, k]} of ONE
    sequence `tokens`, `labels` [1, S] under
    `default_matmul_precision("highest")`; `model` are forward's keywords.
    `grads_of`: also `grads`, the loss's gradient by the parameters: "all"
    (the CPU tests), or a layer's index for that layer's alone (the cell's
    check, where all would not fit)."""
    def run(part, params, tokens, labels):
        if isinstance(grads_of, int):
            layers = list(params["layers"])
            layers[grads_of] = part
            params = {**params, "layers": layers}
        elif grads_of == "all":
            params = part
        logits, chosen = forward(params, tokens, **model)
        value = loss(logits, labels)
        return value, {"logits": logits[None], "loss": value,
                       "chosen": chosen[:, None]}

    args = (params, jnp.asarray(tokens)[0], jnp.asarray(labels)[0])
    with jax.default_matmul_precision("highest"):
        if grads_of is None:
            return jax.jit(run)(None, *args)[1]
        part = params if grads_of == "all" else params["layers"][grads_of]
        (_, out), grads = jax.jit(
            jax.value_and_grad(run, has_aux=True))(part, *args)
    return {**out, "grads": grads}


def routing_flips(got_chosen, want_chosen):
    """How many (layer, token) pairs chose another SET of experts."""
    same = jnp.all(jnp.sort(got_chosen, axis=-1)
                   == jnp.sort(want_chosen, axis=-1), axis=-1)
    return int(jnp.sum(~same)), int(same.size)


def compare(got, want):
    """Errors of `got` against `want` (each {logits, loss, chosen[, grads:
    one layer's {name: gradient}]}), and whether every bounded one is in
    bounds."""
    diff = got["logits"].astype(jnp.float32) - want["logits"]
    flips, pairs = routing_flips(got["chosen"], want["chosen"])
    errs = {
        "logits_rel_rms": float(jnp.sqrt(jnp.mean(diff * diff)
                                         / jnp.mean(want["logits"] ** 2))),
        "logits_max_abs": float(jnp.max(jnp.abs(diff))),
        "loss_abs": abs(float(got["loss"]) - float(want["loss"])),
        "routing_flips": flips,
        "routing_flips_share": flips / pairs,
    }
    ok = (errs["logits_rel_rms"] < TOL_LOGITS_REL_RMS
          and errs["loss_abs"] < TOL_LOSS_ABS
          and errs["routing_flips_share"] < TOL_ROUTING_FLIPS_SHARE)
    if "grads" in want:
        leaves = [k for k in want["grads"] if k not in STATE_LEAVES]
        g = {k: got["grads"][k].astype(jnp.float32) for k in leaves}
        w = {k: want["grads"][k].astype(jnp.float32) for k in leaves}
        norm = lambda t: float(jnp.sqrt(sum(jnp.sum(v * v)
                                            for v in t.values())))
        errs["grad_norm"] = [norm(g), norm(w)]
        by_leaf = errs["grad_rel_by_leaf"] = {
            k: float(jnp.linalg.norm(g[k] - w[k]) / jnp.linalg.norm(w[k]))
            for k in leaves}
        errs["grad_rel_max"] = max(
            v for k, v in by_leaf.items() if k not in ROUTED_LEAVES)
        routed = [by_leaf[k] for k in ROUTED_LEAVES if k in by_leaf]
        if routed:
            errs["grad_rel_max_routed"] = max(routed)
        ok = ok and errs["grad_rel_max"] < TOL_GRAD_REL_MAX
    return errs, ok
