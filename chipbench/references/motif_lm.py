"""Motif-3-Beta's layers as its `config.json` (`model_type: Motif`) describes
them, with the choices it leaves open as `configs/motif3_beta_ep48_d5.json`'s
`assumed` makes them, trained on the next token, in plain float32
`jax.numpy`.  No kernel, no sorting, no grouped product, no remat, no
sharding; independent of `burst_attn_tpu`'s model code.

The residual is four streams X [S, 4, D] (the embedding copied to each).  For
each of a layer's two sublayers F (attention, then the MLP), with eps the
config's rms_norm_eps:
    x = vec(X) / sqrt(mean(vec(X)^2) + eps)                       [S, 4D]
    pre = sigmoid(a0 x P[:, :4] + b[:4]),  post = 2 sigmoid(a1 x P[:, 4:8]
    + b[4:8]),  res = SinkhornKnopp(exp(a2 x P[:, 8:] + b[8:]))  [S, 4, 4]
    X <- clip(res X + post^T F(pre X), +-hidden_clamp)
with SinkhornKnopp 20 rounds of dividing each row by its sum, then each
column.  The streams are summed before the final norm.

Attention (grouped differential latent attention), h = rmsnorm(u) * scale:
    q = rmsnorm(h Wq_a) Wq_b                     -> [80, S, 128 + 64 rope]
    [c | k_r] = h Wkv_a;  [k_nope | v] = rmsnorm(c) Wkv_b  -> [16, S, 128+128]
    k = [k_nope | rope(k_r) for each KV head]; query head i reads KV head i//5
    o_i = softmax(q_i k^T / sqrt(192) + M) v, M causal, or causal within the
      layer's window (the query and the 127 before it)
    d_j = o_signal(j) - sigmoid(h Wl)_j o_noise(group of j), the 64 signal
      heads 0-3 of each group of five, the fifth the noise head
    F = (sigmoid(h Wg) * d) Wo
rope rotates channels (2i, 2i+1) together by position * theta^(-2i/64).

MLP, h = rmsnorm(u) * scale, P_w(v) = 0.5 (w0 n(v) + w1 n(v^2) + w2 n(v^3) +
clip(w3, -0.5, 0.5)), n(z) = z / sqrt(mean(z^2) + eps_p) over the MLP's width:
  the dense layer:  F = (P_w(h Wg) * (h Wu)) Wd
  sparse layers:    s = sigmoid(h Wr);  S = the 8 largest of s + b;
    g_e = 2 s_e / (sum_S s + 1e-20) for e in S (WITHOUT b);
    F = sum over e in S that are HELD of g_e Expert_e(h) + Shared(h), each
    expert and the shared one P_w-gated with its own w.
The loss is the mean next-token cross entropy over the labelled positions.

Departures from the published model, each because the deployment's cut says
so: only the experts `held` here add to a sparse layer's output; the
vocabulary is the slice held; the layers are the five this chip holds; the
bias `b` is a fixed leaf (`router_bias`) and no auxiliary loss is added.

Attention runs a block of query rows at a time (`lax.map`, each block
rematerialised in the backward), so that 80 heads at 4,096 tokens fit beside
the trainer's parameters: [16, 5, 256, S] scores, not [80, S, S].  The held
experts are a `lax.scan` over their stacked weights (every expert sees every
token, weighted by its gate there, 0 where not chosen).

Parameters are the trainer's own pytree (the weights under test, cast up).
"""

from functools import partial

import jax
import jax.numpy as jnp

# Bounds of the system (bf16 weights and activations, float32 router, maps
# and accumulation) against this float32 model at 4,096 tokens, published
# widths, seeded weights, the routers' biases balanced as a cell's run
# balances them.  Each lies between two readings taken on the chip (PERF.md
# section 6), with room to both: the largest the system gave over the
# seeds run (logits 0.024, flips 16.9 %, gradient 0.047, loss 5e-4, mHC
# token sums 0.0014), and what this reference gives against itself with
# every activation rounded through an 8-bit float (`round_to`
# float8_e4m3fn: 0.21-0.25, 90-93 %, 0.39-0.46, 1.6e-3-5.9e-3,
# 0.005-0.025), which the bounds refuse; the reference rounded through
# bfloat16 reads as the system does
# (`python3 -m chipbench.runners.train_motif --seed N`).
TOL_LOGITS_REL_RMS = 0.045
TOL_ROUTING_FLIPS_SHARE = 0.28
TOL_LOSS_ABS = 1e-3
TOL_GRAD_REL_MAX = 0.1
# Leaves whose gradient follows the routing: a flipped eighth choice swaps a
# whole term of theirs (the MLP sublayer's maps feed the routed MLP); reported
# beside the bounded ones, not bounded.
ROUTED_LEAVES = ("router", "mlp_norm", "w_gate", "w_up", "w_down",
                 "shared_gate", "shared_up", "shared_down", "expert_poly",
                 "shared_poly", "mhc_mlp_phi", "mhc_mlp_alpha", "mhc_mlp_bias")
# The mHC maps' gains [3] and biases [2n + n^2] of each sublayer: each
# component's gradient is a sum over the tokens of terms that cancel (sum
# |term| / |sum| 17-1,238 on the seeds run), so its relative error reads how
# far they cancel on the seed: the reference rounded through bfloat16 alone
# reads up to 0.15 on a gain and 0.25 on a bias there.  Each is held
# instead by its error over the norm of its terms' summed magnitudes
# (`reference` takes the terms, [S, ...]), what the error could be were
# every term wholly off: never more than the relative error, and less by as
# far as the terms cancel.  The MLP sublayer's are also routed leaves:
# reported, not bounded.  The bound lies between the system's largest over
# six seeds (0.0014) and the 8-bit reference's smallest over three (0.0053).
TOKEN_SUM_LEAVES = ("mhc_attn_alpha", "mhc_attn_bias", "mhc_mlp_alpha",
                    "mhc_mlp_bias")
TOL_TOKEN_SUM_ERR = 0.004
STATE_LEAVES = ("router_bias",)
QUERY_BLOCK = 256


def _f32(a):
    return a.astype(jnp.float32)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope_interleaved(x, positions, theta):
    """x [..., S, H] with positions [S]: channels (2i, 2i+1) are the real and
    imaginary part of one number, times exp(i * pos * theta^(-2i/H))."""
    h = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, h, 2, dtype=jnp.float32) / h)
    angle = positions.astype(jnp.float32)[:, None] * freqs
    re, im = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.stack([re * cos - im * sin, re * sin + im * cos],
                     axis=-1).reshape(x.shape)


def _attention(q, k, v, window):
    """q [N, S, Dq], k [Nkv, S, Dq], v [Nkv, S, Dv] -> [N, S, Dv]; query head
    i reads KV head i // (N / Nkv); causal, and within `window` where set."""
    n, s_len, dq = q.shape
    n_kv = k.shape[0]
    block = min(QUERY_BLOCK, s_len)
    q = q.reshape(n_kv, n // n_kv, s_len, dq)
    keys = jnp.arange(s_len)

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=2)
        scores = jnp.einsum("kgqd,ksd->kgqs", qb, k) / jnp.sqrt(dq)
        pos = start + jnp.arange(block)
        seen = keys[None, :] <= pos[:, None]
        if window is not None:
            seen = seen & (keys[None, :] > pos[:, None] - window)
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("kgqs,ksd->kgqd", jax.nn.softmax(scores, axis=-1),
                          v)

    out = jax.lax.map(rows, jnp.arange(0, s_len, block))  # [blocks, ...]
    return jnp.moveaxis(out, 0, 2).reshape(n, s_len, v.shape[-1])


def _gdla(h, p, positions, *, qk_nope, kv_latent, rope_theta, eps, window,
          rnd):
    """The attention sublayer's output [S, D] from its normed input h."""
    q = jnp.einsum("sc,cnh->nsh", rnd(_rms_norm(h @ _f32(p["wq_a"]),
                                                p["q_a_norm"], eps)),
                   _f32(p["wq_b"]))
    q = rnd(jnp.concatenate(
        [q[..., :qk_nope],
         _rope_interleaved(q[..., qk_nope:], positions, rope_theta)],
        axis=-1))
    down = h @ _f32(p["wkv_a"])
    latent = rnd(_rms_norm(down[:, :kv_latent], p["kv_norm"], eps))
    up = jnp.einsum("sc,cnh->nsh", latent, _f32(p["wkv_b"]))
    k_rope = _rope_interleaved(down[:, kv_latent:], positions, rope_theta)
    n_kv = up.shape[0]
    k = rnd(jnp.concatenate(
        [up[..., :qk_nope], jnp.broadcast_to(k_rope, (n_kv, *k_rope.shape))],
        axis=-1))
    v = rnd(up[..., qk_nope:])
    o = rnd(_attention(q, k, v, window))               # [N, S, Dv]
    n = o.shape[0]
    per = n // n_kv
    signal = jnp.stack([o[g * per:g * per + per - 1] for g in range(n_kv)])
    noise = jnp.stack([o[g * per + per - 1] for g in range(n_kv)])
    lam = jax.nn.sigmoid(h @ _f32(p["w_lambda"])).T    # [signal heads, S]
    diff = signal - lam.reshape(n_kv, per - 1, -1)[..., None] * noise[:, None]
    gate = jax.nn.sigmoid(jnp.einsum("sd,dnh->nsh", h,
                                     _f32(p["w_attn_gate"])))
    d = rnd(gate * diff.reshape(gate.shape))
    return jnp.einsum("nsh,nhd->sd", d, _f32(p["wo"]))


def poly_norm(v, w, *, scale, clamp, eps):
    """The published PolyNorm of v [..., F] with weights w [4] (w1, w2, w3,
    b), over v's last axis."""
    def n(z):
        return z / jnp.sqrt(jnp.mean(z * z, axis=-1, keepdims=True) + eps)

    return scale * (w[0] * n(v) + w[1] * n(v ** 2) + w[2] * n(v ** 3)
                    + jnp.clip(w[3], -clamp, clamp))


def _mlp(h, w_gate, w_up, w_down, w_act, act):
    return (poly_norm(h @ _f32(w_gate), _f32(w_act), **act)
            * (h @ _f32(w_up))) @ _f32(w_down)


def _experts(h, p, *, held, top_k, gate_scale, act):
    """(the held chosen experts' part + the shared expert, the chosen sets
    [S, k]) of one sparse layer on h [S, D]."""
    s = jax.nn.sigmoid(h @ _f32(p["router"]))
    _, choice = jax.lax.top_k(s + p["router_bias"], top_k)
    gates = jnp.take_along_axis(s, choice, axis=-1)
    gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    gates = gates * gate_scale

    def add_expert(y, expert):
        e, w_gate, w_up, w_down, w_act = expert
        g_e = jnp.sum(jnp.where(choice == e, gates, 0.0), axis=-1)
        return y + g_e[:, None] * _mlp(h, w_gate, w_up, w_down, w_act,
                                       act), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                        (jnp.arange(*held), p["w_gate"], p["w_up"],
                         p["w_down"], p["expert_poly"]))
    shared = _mlp(h, p["shared_gate"], p["shared_up"], p["shared_down"],
                  p["shared_poly"], act)
    return y + shared, choice


def sinkhorn_knopp(m, iters):
    """Rows then columns of the positive m [..., n, n] divided by their
    sums, `iters` times (a loop: one body in the program, not `iters`)."""
    def normalise(_, m):
        m = m / jnp.sum(m, axis=-1, keepdims=True)
        return m / jnp.sum(m, axis=-2, keepdims=True)

    return jax.lax.fori_loop(0, iters, normalise, m)


def mhc_maps(streams, p, sub, *, eps, iters):
    """(pre [S, n], post [S, n], res [S, n, n]) of the streams [S, n, D] for
    sublayer `sub` ("attn" or "mlp").  The gains `mhc_<sub>_alpha` [3] and
    biases `mhc_<sub>_bias` [2n + n^2] may be one a token, [S, ...]
    (`reference` takes their per-token gradient so)."""
    s_len, n, d = streams.shape
    x = streams.reshape(s_len, n * d)
    x = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    a, b = p[f"mhc_{sub}_alpha"], p[f"mhc_{sub}_bias"]
    proj = x @ p[f"mhc_{sub}_phi"]
    pre = jax.nn.sigmoid(a[..., 0:1] * proj[:, :n] + b[..., :n])
    post = 2.0 * jax.nn.sigmoid(a[..., 1:2] * proj[:, n:2 * n]
                                + b[..., n:2 * n])
    res = jnp.exp(a[..., 2:3] * proj[:, 2 * n:] + b[..., 2 * n:]).reshape(
        s_len, n, n)
    return pre, post, sinkhorn_knopp(res, iters)


def rounding(round_to):
    """a -> a rounded through the float type `round_to` in the forward, as
    its cast would round it, the identity in the backward (None: a).  Not a
    float32 -> `round_to` -> float32 round trip: the chip drops one inside a
    fusion as excess precision (PERF.md section 6), and keeps
    `reduce_precision`.  That rounds the type's normal numbers; a type
    narrower than float32's range (an 8-bit float, without infinities: it
    uses its top exponent, so one exponent bit is spared) also takes its
    subnormal step below them and is not a number beyond its largest, as
    its cast."""
    if round_to is None:
        return lambda a: a
    info = jnp.finfo(round_to)
    narrow = info.nexp < jnp.finfo(jnp.float32).nexp
    bits = info.nexp + 1 if narrow else info.nexp
    tiny, step = float(info.smallest_normal), float(info.smallest_subnormal)

    def low(a):
        r = jax.lax.reduce_precision(a, exponent_bits=bits,
                                     mantissa_bits=info.nmant)
        if narrow:
            r = jnp.where(jnp.abs(a) < tiny, jnp.round(a / step) * step, r)
            r = jnp.where(jnp.abs(r) > float(info.max), jnp.nan, r)
        return r

    return lambda a: a + jax.lax.stop_gradient(low(a) - a)


def forward(params, tokens, *, held, top_k, gate_scale, qk_nope, kv_latent,
            rope_theta, rms_norm_eps, windows, streams, sinkhorn_iters,
            hidden_clamp, poly_scale, poly_clamp, poly_eps, round_to=None):
    """tokens [S] (one sequence, positions 0..S-1) -> (float32 logits [S, V],
    the sparse layers' chosen expert sets [sparse layers, S, k]).
    `windows`: each layer's window (None: causal).  `round_to`: a dtype every
    activation is rounded through (`rounding`: the lower-precision readings
    the bounds are set against); None computes in float32."""
    rnd = rounding(round_to)
    positions = jnp.arange(tokens.shape[0])
    eps = rms_norm_eps
    act = dict(scale=poly_scale, clamp=poly_clamp, eps=poly_eps)

    def sublayer(x, p, sub, fn):
        pre, post, res = mhc_maps(x, p, sub, eps=eps, iters=sinkhorn_iters)
        out, extra = fn(rnd(jnp.einsum("sn,snd->sd", pre, x)))
        x = (jnp.einsum("sij,sjd->sid", res, x)
             + post[:, :, None] * rnd(out)[:, None, :])
        return rnd(jnp.clip(x, -hidden_clamp, hidden_clamp)), extra

    def layer(x, p, window):
        def attend(u):
            h = rnd(_rms_norm(u, p["attn_norm"], eps))
            return _gdla(h, p, positions, qk_nope=qk_nope,
                         kv_latent=kv_latent, rope_theta=rope_theta, eps=eps,
                         window=window, rnd=rnd), None

        def mlp(u):
            h = rnd(_rms_norm(u, p["mlp_norm"], eps))
            if "router" in p:
                return _experts(h, p, held=held, top_k=top_k,
                                gate_scale=gate_scale, act=act)
            return _mlp(h, p["w_gate"], p["w_up"], p["w_down"], p["poly"],
                        act), None

        x, _ = sublayer(x, p, "attn", attend)
        return sublayer(x, p, "mlp", mlp)

    x = rnd(_f32(params["embed"])[tokens])
    x = jnp.broadcast_to(x[:, None], (x.shape[0], streams, x.shape[1]))
    chosen = []
    for p, window in zip(params["layers"], windows):
        x, choice = layer(x, p, window)
        if choice is not None:
            chosen.append(choice)
    x = rnd(_rms_norm(jnp.sum(x, axis=1), params["final_norm"], eps))
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


def _run(part, params, tokens, labels, grads_of, model):
    if isinstance(grads_of, int):
        layers = list(params["layers"])
        layers[grads_of] = part
        params = {**params, "layers": layers}
    elif grads_of == "all":
        params = part
    logits, chosen = forward(params, tokens, **dict(model))
    value = loss(logits, labels)
    return value, {"logits": logits[None], "loss": value,
                   "chosen": chosen[:, None]}


@partial(jax.jit, static_argnames=("grads_of", "model"))
def _outputs(part, params, tokens, labels, grads_of, model):
    run = partial(_run, grads_of=grads_of, model=model)
    if grads_of is None:
        return run(part, params, tokens, labels)[1]
    (_, out), grads = jax.value_and_grad(run, has_aux=True)(
        part, params, tokens, labels)
    return {**out, "grads": grads}


def reference(params, tokens, labels, grads_of=None, **model):
    """{logits [1, S, V], loss, chosen [sparse layers, 1, S, k]} of ONE
    sequence `tokens`, `labels` [1, S] under
    `default_matmul_precision("highest")`; `model` are forward's keywords.
    `grads_of`: also `grads`, the loss's gradient by the parameters: "all"
    (the CPU tests), or a layer's index for that layer's alone (the cell's
    check, where all would not fit), its TOKEN_SUM_LEAVES then one a token
    ([S, ...]: each token's term of the sum that is the gradient).  One
    program for each `grads_of` and `model`, compiled once a process."""
    tokens, labels = jnp.asarray(tokens)[0], jnp.asarray(labels)[0]
    part = params if grads_of == "all" else None
    if isinstance(grads_of, int):
        part = {k: jnp.broadcast_to(v, (tokens.shape[0], *v.shape))
                if k in TOKEN_SUM_LEAVES else v
                for k, v in params["layers"][grads_of].items()}
    with jax.default_matmul_precision("highest"):
        return _outputs(part, params, tokens, labels, grads_of=grads_of,
                        model=tuple(sorted(model.items())))


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
        terms = {k: want["grads"][k].astype(jnp.float32)
                 for k in TOKEN_SUM_LEAVES
                 if k in want["grads"] and want["grads"][k].ndim == 2}

        def total(k, v):
            v = v.astype(jnp.float32)
            return v.sum(axis=0) if k in terms and v.ndim == 2 else v

        g = {k: total(k, got["grads"][k]) for k in leaves}
        w = {k: total(k, want["grads"][k]) for k in leaves}
        norm = lambda t: float(jnp.sqrt(sum(jnp.sum(v * v)
                                            for v in t.values())))
        errs["grad_norm"] = [norm(g), norm(w)]
        by_leaf = errs["grad_rel_by_leaf"] = {
            k: float(jnp.linalg.norm(g[k] - w[k]) / jnp.linalg.norm(w[k]))
            for k in leaves}
        errs["grad_rel_max"] = max(
            v for k, v in by_leaf.items()
            if k not in ROUTED_LEAVES + TOKEN_SUM_LEAVES)
        routed = [by_leaf[k] for k in ROUTED_LEAVES if k in by_leaf]
        if routed:
            errs["grad_rel_max_routed"] = max(routed)
        ok = ok and errs["grad_rel_max"] < TOL_GRAD_REL_MAX
        if terms:
            # the error over the norm of the terms' summed magnitudes (what
            # it could be were every term wholly off): never more than the
            # relative error, and less by as far as the terms cancel, which
            # is the norm of those magnitudes over the sum's; and where `got`
            # has its terms too (a rounded reference), how far its terms are
            # off, summed the same way: the first one's ceiling
            mass = {k: jnp.sum(jnp.abs(t), axis=0) for k, t in terms.items()}
            errs["token_sum_err"] = {
                k: float(jnp.linalg.norm(g[k] - w[k])
                         / jnp.linalg.norm(mass[k])) for k in terms}
            errs["token_sum_cancellation"] = {
                k: float(jnp.linalg.norm(mass[k]) / jnp.linalg.norm(w[k]))
                for k in terms}
            own = {k: got["grads"][k].astype(jnp.float32) for k in terms
                   if got["grads"][k].ndim == 2}
            if own:
                errs["token_term_err"] = {
                    k: float(jnp.linalg.norm(jnp.sum(jnp.abs(v - terms[k]),
                                                     axis=0))
                             / jnp.linalg.norm(mass[k]))
                    for k, v in own.items()}
            errs["token_sum_err_max"] = max(
                v for k, v in errs["token_sum_err"].items()
                if k not in ROUTED_LEAVES)
            ok = ok and errs["token_sum_err_max"] < TOL_TOKEN_SUM_ERR
    return errs, ok
