"""mHC's stream mixing (arXiv 2512.24880) as Pallas TPU kernels.

A sublayer pass of mHC reads the streams x [B, S, n d] twice: `mhc_pre`
makes the maps of x and the sublayer's input u = sum_i pre_i x_i, and
`mhc_post` makes the streams after the sublayer, clamp(res x + post f), from
x and the sublayer's output f.  Each is one kernel over tiles of tokens that
reads the streams once and writes what it makes once, in their dtype;
float32 lives in VMEM only.  Each has a `jax.custom_vjp` whose backward is a
kernel of the same kind:

  * `mhc_post_bwd` reads x, f, the maps and dy, and writes dx, df and the
    maps' cotangent (the clamp's mask applied);
  * `mhc_pre_bwd` reads x, du, the maps' cotangent and the dx that
    `mhc_post_bwd` wrote (mhc_pre hands x on to mhc_post, so that its
    cotangent arrives here), recomputes the maps and Sinkhorn-Knopp's rounds,
    writes dx once, and sums the cotangents of phi, the gains and the biases
    over the token grid.

The maps are computed from the one read of x: RMSNorm over n d is a scale a
token, so proj = (x phi) rsqrt(mean x^2 + eps) needs no normed copy.  They
travel between the kernels packed, 128 float32 lanes a token (`_runs`):
transposed, each of their groups starts on a tile of eight sublanes, where
Sinkhorn-Knopp runs a token to a lane.

The model's wiring (models/transformer.py) calls these on the TPU and the
jnp `_mhc_pre` / `_mhc_post` elsewhere; those stay the oracle of the tests.
"""

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import obs

LANES = 128
# tokens a tile: the packed maps' transposes are LANES x TOKENS
TOKENS = 128
# lanes of a stream that one step of the kernels' inner loops reads
CHUNK = 512
VMEM_LIMIT = 100 * 1024 * 1024

_M_FUSED = obs.counter(
    "mhc.fused",
    "mHC sublayer passes traced through the fused kernels (ops/mhc.py), by "
    "op: pre (maps and the sublayer's input) or post (the streams after the "
    "sublayer); counted at trace time")


def engaged() -> bool:
    """Whether the model's mHC passes take these kernels: on the TPU, the
    rule burst_attn's backend="auto" has."""
    return jax.default_backend() == "tpu"


def tiles(seq: int, d: int, n: int) -> bool:
    """Whether streams of `seq` tokens, n of width d, fit the kernels'
    tiling."""
    return seq % TOKENS == 0 and d % LANES == 0 and 1 <= n <= 8


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _runs(n):
    """(lane, column) of each run of n of phi's 2n + n^2 columns (pre, post,
    then res row by row, as transformer.mhc_maps reads them) in the packed
    maps, in phi's order: pre_i at lane i, post_i at 8 (n + 1) + i, res_ij
    at 8 (i + 1) + j."""
    return [(0, 0), (8 * (n + 1), n),
            *((8 * (i + 1), 2 * n + i * n) for i in range(n))]


def _lanes(n):
    """The packed lane of each of phi's columns."""
    return [lane + j for lane, _ in _runs(n) for j in range(n)]


def _to_lanes(a, n, axis):
    """a [.., 2n + n^2, ..] along `axis` -> [.., LANES, ..]: each run at its
    lanes, zero between (slices and a concatenation: no scatter)."""
    pieces, at = [], 0
    for lane, col in sorted(_runs(n)):
        gap = list(a.shape)
        gap[axis] = lane - at
        pieces += [jnp.zeros(gap, a.dtype),
                   jax.lax.slice_in_dim(a, col, col + n, axis=axis)]
        at = lane + n
    gap = list(a.shape)
    gap[axis] = LANES - at
    return jnp.concatenate(pieces + [jnp.zeros(gap, a.dtype)], axis)


def _from_lanes(a, n, axis):
    """The inverse of _to_lanes: [.., >= 8 (n + 2), ..] -> [.., 2n + n^2,
    ..] in phi's column order."""
    return jnp.concatenate([jax.lax.slice_in_dim(a, lane, lane + n, axis=axis)
                            for lane, _ in _runs(n)], axis)


def unpack_maps(maps, n):
    """(pre [..., n], post [..., n], res [..., n, n]) of packed maps."""
    w = _from_lanes(maps, n, maps.ndim - 1)
    return (w[..., :n], w[..., n:2 * n],
            w[..., 2 * n:].reshape(*maps.shape[:-1], n, n))


def _pack(phi, alpha, bias, n, dtype):
    """phi [n d, 2n + n^2] as the kernels' [n d, LANES] in `dtype` (the
    streams'), and the gain and bias of each lane [1, LANES] float32."""
    gain = jnp.repeat(alpha.astype(jnp.float32), np.array([n, n, n * n]),
                      total_repeat_length=2 * n + n * n)
    return (_to_lanes(phi.astype(dtype), n, 1), _to_lanes(gain, n, 0)[None],
            _to_lanes(bias.astype(jnp.float32), n, 0)[None])


# ---------------------------------------------------------------------------
# in-kernel pieces


def _fold(a):
    """[rows, k LANES] -> [rows, LANES]: its lane tiles summed."""
    out = a[:, :LANES]
    for k in range(LANES, a.shape[1], LANES):
        out = out + a[:, k:k + LANES]
    return out


def _chunks(d, body, carry=None):
    """body(cols, carry) -> carry over the chunks of a stream of width d, a
    loop (one body in the kernel, not d / CHUNK): cols(i) is chunk's lanes
    in stream i, cols(None) in a [.., d] array."""
    dc = math.gcd(d, CHUNK)

    def step(k, carry):
        def cols(i):
            at = k * dc if i is None else i * d + k * dc
            return pl.ds(pl.multiple_of(at, dc), dc)
        return body(cols, carry)

    return jax.lax.fori_loop(0, d // dc, step, carry)


def _f32(ref, cols):
    return ref[:, cols].astype(jnp.float32)


def _logits(x_ref, phi_ref, gain_ref, bias_ref, n, d, eps):
    """(logits, proj, x phi, rsqrt(mean x^2 + eps)) of a tile of tokens,
    [tt, LANES] in the packed lanes but the last [tt, 1]."""
    zero = jnp.zeros((x_ref.shape[0], LANES), jnp.float32)

    def body(cols, acc):
        z, sq = acc
        for i in range(n):
            xc = x_ref[:, cols(i)]
            z = z + jnp.dot(xc, phi_ref[cols(i), :],
                            preferred_element_type=jnp.float32)
            xc = xc.astype(jnp.float32)
            sq = sq + _fold(xc * xc)
        return z, sq

    z, sq = _chunks(d, body, (zero, zero))
    r = jax.lax.rsqrt(jnp.sum(sq, axis=1, keepdims=True) / (n * d) + eps)
    proj = z * r
    return proj * gain_ref[...] + bias_ref[...], proj, z, r


def _lane_kinds(tt, n):
    lane = jax.lax.broadcasted_iota(jnp.int32, (tt, LANES), 1)
    return lane, lane < n, (lane >= 8 * (n + 1)) & (lane < 8 * (n + 1) + n)


def _exp_rows(logits_t, n):
    """exp of the res logits, row i of every token's n x n as [8, tt] (its
    j on sublanes, zero from n on), over their largest; and that mask."""
    tt = logits_t.shape[1]
    valid = jax.lax.broadcasted_iota(jnp.int32, (8, tt), 0) < n
    rows = [logits_t[8 * (i + 1):8 * (i + 2)] for i in range(n)]
    top = functools.reduce(jnp.maximum, [
        jnp.max(jnp.where(valid, r, -jnp.inf), axis=0, keepdims=True)
        for r in rows])
    return [jnp.where(valid, jnp.exp(r - top), 0.0) for r in rows], valid


def _sk_round(m, valid):
    """One Sinkhorn-Knopp round on the rows `m`: rows, then columns."""
    a = [r / jnp.sum(r, axis=0, keepdims=True) for r in m]
    cols = jnp.where(valid, functools.reduce(jnp.add, a), 1.0)
    return [r / cols for r in a]


def _sk_round_bwd(m, dout, valid):
    """The cotangent of a round's input rows `m` from its output's."""
    s = [jnp.sum(r, axis=0, keepdims=True) for r in m]
    a = [r / t for r, t in zip(m, s)]
    cols = jnp.where(valid, functools.reduce(jnp.add, a), 1.0)
    dot = functools.reduce(jnp.add, [g * r / cols for g, r in zip(dout, a)])
    da = [(g - dot) / cols for g in dout]
    return [jnp.where(valid, (g - jnp.sum(g * r, axis=0, keepdims=True)) / t,
                      0.0) for g, r, t in zip(da, a, s)]


def _rows_to_lanes(rows, n, tt):
    """n rows [8, tt] -> [tt, LANES], row i at lanes 8 (i + 1) .. (the res
    lanes), zero elsewhere."""
    zeros = lambda k: jnp.zeros((k, tt), jnp.float32)
    return jnp.concatenate([zeros(8), *rows, zeros(LANES - 8 * (n + 1))],
                           axis=0).T


def _maps(logits, n, iters):
    """The packed maps [tt, LANES] of a tile's logits."""
    tt = logits.shape[0]
    _, pre, post = _lane_kinds(tt, n)
    sig = jax.nn.sigmoid(logits)
    e, valid = _exp_rows(logits.T, n)
    res = jax.lax.fori_loop(0, iters,
                            lambda _, m: tuple(_sk_round(list(m), valid)),
                            tuple(e))
    return jnp.where(pre, sig, jnp.where(post, 2.0 * sig,
                                         _rows_to_lanes(res, n, tt)))


def _mix_columns(maps, n):
    """post_i and res_ij of packed maps, each [tt, 1]."""
    lanes = _lanes(n)
    column = lambda k: maps[:, k:k + 1]
    return ([column(lanes[n + i]) for i in range(n)],
            [[column(lanes[2 * n + i * n + j]) for j in range(n)]
             for i in range(n)])


def _lane_sums(accs, lanes, tt):
    """[tt, LANES] with each acc's lane sum at its lane, zero elsewhere."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (tt, LANES), 1)
    return functools.reduce(jnp.add, [
        jnp.where(lane == k, jnp.sum(a, axis=1, keepdims=True), 0.0)
        for k, a in zip(lanes, accs)])


# ---------------------------------------------------------------------------
# kernels


def _pre_fwd_kernel(x_ref, phi_ref, gain_ref, bias_ref, u_ref, maps_ref, *,
                    n, d, eps, iters):
    logits, _, _, _ = _logits(x_ref, phi_ref, gain_ref, bias_ref, n, d, eps)
    maps = _maps(logits, n, iters)
    maps_ref[...] = maps
    pre = [maps[:, i:i + 1] for i in range(n)]

    def body(cols, _):
        u = functools.reduce(jnp.add, [pre[i] * _f32(x_ref, cols(i))
                                       for i in range(n)])
        u_ref[:, cols(None)] = u.astype(u_ref.dtype)

    _chunks(d, body)


def _post_fwd_kernel(x_ref, maps_ref, f_ref, y_ref, *, n, d, clamp):
    post, res = _mix_columns(maps_ref[...], n)

    def body(cols, _):
        xs = [_f32(x_ref, cols(j)) for j in range(n)]
        f = _f32(f_ref, cols(None))
        for i in range(n):
            y = functools.reduce(jnp.add, [res[i][j] * xs[j]
                                           for j in range(n)]) + post[i] * f
            if clamp is not None:
                y = jnp.clip(y, -clamp, clamp)
            y_ref[:, cols(i)] = y.astype(y_ref.dtype)

    _chunks(d, body)


def _post_bwd_kernel(x_ref, maps_ref, f_ref, dy_ref, dx_ref, df_ref, dm_ref,
                     acc_ref, *, n, d, clamp):
    """acc_ref [n + n^2, tt, LANES]: post's then res's cotangents, lane tiles
    not yet summed."""
    post, res = _mix_columns(maps_ref[...], n)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(cols, _):
        xs = [_f32(x_ref, cols(j)) for j in range(n)]
        f = _f32(f_ref, cols(None))
        dxs, df = [0.0] * n, 0.0
        for i in range(n):
            g = _f32(dy_ref, cols(i))
            if clamp is not None:
                y = functools.reduce(jnp.add, [res[i][j] * xs[j]
                                               for j in range(n)])
                g = jnp.where(jnp.abs(y + post[i] * f) <= clamp, g, 0.0)
            acc_ref[i] += _fold(g * f)
            for j in range(n):
                acc_ref[n + i * n + j] += _fold(g * xs[j])
                dxs[j] = dxs[j] + res[i][j] * g
            df = df + post[i] * g
        for j in range(n):
            dx_ref[:, cols(j)] = dxs[j].astype(dx_ref.dtype)
        df_ref[:, cols(None)] = df.astype(df_ref.dtype)

    _chunks(d, body)
    dm_ref[...] = _lane_sums([acc_ref[k] for k in range(n + n * n)],
                             _lanes(n)[n:], maps_ref.shape[0])


def _pre_bwd_kernel(x_ref, du_ref, dm_ref, dxin_ref, phi_ref, gain_ref,
                    bias_ref, dx_ref, dphi_ref, dgain_ref, dbias_ref,
                    acc_ref, rounds_ref, *, n, d, eps, iters):
    """acc_ref [n, tt, LANES]: pre's cotangents, lane tiles not yet summed;
    rounds_ref [iters, 8 n, tt]: each Sinkhorn-Knopp round's input."""
    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        dphi_ref[...] = jnp.zeros_like(dphi_ref)
        dgain_ref[...] = jnp.zeros_like(dgain_ref)
        dbias_ref[...] = jnp.zeros_like(dbias_ref)

    logits, proj, z, r = _logits(x_ref, phi_ref, gain_ref, bias_ref, n, d,
                                 eps)
    tt = logits.shape[0]
    _, pre_lane, post_lane = _lane_kinds(tt, n)
    sig = jax.nn.sigmoid(logits)
    # the cotangent of pre: du . x_i
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def dpre(cols, _):
        du = _f32(du_ref, cols(None))
        for i in range(n):
            acc_ref[i] += _fold(du * _f32(x_ref, cols(i)))

    _chunks(d, dpre)
    dm = dm_ref[...] + _lane_sums([acc_ref[i] for i in range(n)], range(n),
                                  tt)
    # through the sigmoids, and through Sinkhorn-Knopp's rounds and exp: the
    # rounds run again forward, each one's input kept, then backward
    dlogits = dm * sig * (1.0 - sig)
    dlogits = jnp.where(pre_lane, dlogits,
                        jnp.where(post_lane, 2.0 * dlogits, 0.0))
    e, valid = _exp_rows(logits.T, n)

    def forward(k, m):
        rounds_ref[k] = jnp.concatenate(m, axis=0)
        return tuple(_sk_round(list(m), valid))

    def backward(k, g):
        m = rounds_ref[iters - 1 - k]
        return tuple(_sk_round_bwd([m[8 * i:8 * (i + 1)] for i in range(n)],
                                   list(g), valid))

    jax.lax.fori_loop(0, iters, forward, tuple(e))
    dm_t = dm.T
    de = jax.lax.fori_loop(0, iters, backward, tuple(
        dm_t[8 * (i + 1):8 * (i + 2)] for i in range(n)))
    dlogits = dlogits + _rows_to_lanes([g * m for g, m in zip(de, e)], n, tt)
    # through the gains, the biases, the projection and the norm's scale
    dgain_ref[...] += jnp.sum(dlogits * proj, axis=0, keepdims=True)
    dbias_ref[...] += jnp.sum(dlogits, axis=0, keepdims=True)
    dproj = dlogits * gain_ref[...]
    dss = (jnp.sum(dproj * z, axis=1, keepdims=True) * (-0.5 / (n * d))
           * r * r * r)
    dz = dproj * r
    dz_t = dz.T[:dphi_ref.shape[0]].astype(x_ref.dtype)
    dz = dz.astype(phi_ref.dtype)
    pre = [sig[:, i:i + 1] for i in range(n)]

    def dx(cols, _):
        du = _f32(du_ref, cols(None))
        for i in range(n):
            xc = x_ref[:, cols(i)]
            g = (_f32(dxin_ref, cols(i)) + pre[i] * du
                 + jax.lax.dot_general(
                     dz, phi_ref[cols(i), :], (((1,), (1,)), ((), ())),
                     preferred_element_type=jnp.float32)
                 + 2.0 * dss * xc.astype(jnp.float32))
            dx_ref[:, cols(i)] = g.astype(dx_ref.dtype)
            dphi_ref[:, cols(i)] += jnp.dot(
                dz_t, xc, preferred_element_type=jnp.float32)

    _chunks(d, dx)


# ---------------------------------------------------------------------------
# launches: one module-level jit each, so that a kernel body is traced once a
# distinct call and not once a layer (every layer's call has the same avals)


def _rows(width):
    return pl.BlockSpec((None, TOKENS, width), lambda b, s: (b, s, 0))


def _whole(shape):
    return pl.BlockSpec(shape, lambda b, s: (0,) * len(shape))


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


def _pre_fwd_call(x, phi_p, gain, bias, *, n, eps, iters, interpret):
    b, s, k = x.shape
    d = k // n
    return pl.pallas_call(
        functools.partial(_pre_fwd_kernel, n=n, d=d, eps=eps, iters=iters),
        name="mhc_pre_fwd",
        grid=(b, s // TOKENS),
        in_specs=[_rows(k), _whole((k, LANES)), _whole((1, LANES)),
                  _whole((1, LANES))],
        out_specs=[_rows(d), _rows(LANES)],
        out_shape=[jax.ShapeDtypeStruct((b, s, d), x.dtype),
                   jax.ShapeDtypeStruct((b, s, LANES), jnp.float32)],
        compiler_params=_params("parallel", "parallel"),
        interpret=interpret,
    )(x, phi_p, gain, bias)


def _pre_bwd_call(x, du, dm, dx, phi_p, gain, bias, *, n, eps, iters,
                  interpret):
    b, s, k = x.shape
    d = k // n
    rows = 8 * (n + 2)  # the packed lanes phi's columns take, and below
    return pl.pallas_call(
        functools.partial(_pre_bwd_kernel, n=n, d=d, eps=eps, iters=iters),
        name="mhc_pre_bwd",
        grid=(b, s // TOKENS),
        in_specs=[_rows(k), _rows(d), _rows(LANES), _rows(k),
                  _whole((k, LANES)), _whole((1, LANES)), _whole((1, LANES))],
        out_specs=[_rows(k), _whole((rows, k)), _whole((1, LANES)),
                   _whole((1, LANES))],
        out_shape=[jax.ShapeDtypeStruct((b, s, k), dx.dtype),
                   jax.ShapeDtypeStruct((rows, k), jnp.float32),
                   jax.ShapeDtypeStruct((1, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((1, LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, TOKENS, LANES), jnp.float32),
                        pltpu.VMEM((iters, 8 * n, TOKENS), jnp.float32)],
        # dx is written where post's backward left its share, tile by tile
        input_output_aliases={3: 0},
        # phi's, the gains' and the biases' cotangents sum over every tile
        compiler_params=_params("arbitrary", "arbitrary"),
        interpret=interpret,
    )(x, du, dm, dx, phi_p, gain, bias)


def _post_fwd_call(x, maps, f, *, n, clamp, interpret):
    b, s, k = x.shape
    d = k // n
    return pl.pallas_call(
        functools.partial(_post_fwd_kernel, n=n, d=d, clamp=clamp),
        name="mhc_post_fwd",
        grid=(b, s // TOKENS),
        in_specs=[_rows(k), _rows(LANES), _rows(d)],
        out_specs=_rows(k),
        out_shape=jax.ShapeDtypeStruct((b, s, k), x.dtype),
        compiler_params=_params("parallel", "parallel"),
        interpret=interpret,
    )(x, maps, f)


def _post_bwd_call(x, maps, f, dy, *, n, clamp, interpret):
    b, s, k = x.shape
    d = k // n
    return pl.pallas_call(
        functools.partial(_post_bwd_kernel, n=n, d=d, clamp=clamp),
        name="mhc_post_bwd",
        grid=(b, s // TOKENS),
        in_specs=[_rows(k), _rows(LANES), _rows(d), _rows(k)],
        out_specs=[_rows(k), _rows(d), _rows(LANES)],
        out_shape=[jax.ShapeDtypeStruct((b, s, k), x.dtype),
                   jax.ShapeDtypeStruct((b, s, d), f.dtype),
                   jax.ShapeDtypeStruct((b, s, LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n + n * n, TOKENS, LANES), jnp.float32)],
        compiler_params=_params("parallel", "parallel"),
        interpret=interpret,
    )(x, maps, f, dy)


_PRE, _POST = ("n", "eps", "iters", "interpret"), ("n", "clamp", "interpret")
_pre_fwd_launch = jax.jit(_pre_fwd_call, static_argnames=_PRE)
_pre_bwd_launch = jax.jit(_pre_bwd_call, static_argnames=_PRE)
_post_fwd_launch = jax.jit(_post_fwd_call, static_argnames=_POST)
_post_bwd_launch = jax.jit(_post_bwd_call, static_argnames=_POST)


# ---------------------------------------------------------------------------
# the two operations and their backward


class _Mix(NamedTuple):
    n: int
    eps: float
    iters: int
    clamp: Optional[float]
    interpret: bool


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _pre(x, phi, alpha, bias, mix):
    return _pre_fwd(x, phi, alpha, bias, mix)[0]


def _pre_fwd(x, phi, alpha, bias, mix):
    u, maps = _pre_fwd_launch(x, *_pack(phi, alpha, bias, mix.n, x.dtype),
                              n=mix.n, eps=mix.eps, iters=mix.iters,
                              interpret=mix.interpret)
    return (u, maps, x), (x, phi, alpha, bias)


def _pre_bwd(mix, saved, cotangents):
    x, phi, alpha, bias = saved
    du, dm, dx = cotangents
    dx, dphi_t, dgain, dbias = _pre_bwd_launch(
        x, du, dm, dx.astype(x.dtype), *_pack(phi, alpha, bias, mix.n,
                                              x.dtype),
        n=mix.n, eps=mix.eps, iters=mix.iters, interpret=mix.interpret)
    n = mix.n
    dgain = _from_lanes(dgain[0], n, 0)
    dalpha = jnp.stack([jnp.sum(dgain[:n]), jnp.sum(dgain[n:2 * n]),
                        jnp.sum(dgain[2 * n:])])
    return (dx, _from_lanes(dphi_t, n, 0).T.astype(phi.dtype),
            dalpha.astype(alpha.dtype),
            _from_lanes(dbias[0], n, 0).astype(bias.dtype))


_pre.defvjp(_pre_fwd, _pre_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _post(x, maps, f, mix):
    return _post_fwd_launch(x, maps, f, n=mix.n, clamp=mix.clamp,
                            interpret=mix.interpret)


def _post_fwd(x, maps, f, mix):
    return _post(x, maps, f, mix), (x, maps, f)


def _post_bwd(mix, saved, dy):
    x, maps, f = saved
    dx, df, dm = _post_bwd_launch(x, maps, f, dy.astype(x.dtype), n=mix.n,
                                  clamp=mix.clamp, interpret=mix.interpret)
    return dx, dm, df


_post.defvjp(_post_fwd, _post_bwd)


def mhc_pre(x, phi, alpha, bias, *, streams: int, eps: float, iters: int):
    """A sublayer pass's maps of the streams x [B, S, n d] (n = `streams`)
    and its input: (u [B, S, d] = sum_i pre_i x_i in x's dtype, the maps
    packed [B, S, LANES] float32 (`unpack_maps`), x).  phi [n d, 2n + n^2],
    alpha [3] and bias [2n + n^2] are transformer.mhc_maps's; `iters`
    Sinkhorn-Knopp rounds.  Hand the x returned to `mhc_post`: its
    cotangent is how mhc_post's backward gives this one its share of dx."""
    _M_FUSED.inc(op="pre")
    return _pre(x, phi, alpha, bias, _Mix(streams, eps, iters, None,
                                          _interpret()))


def mhc_post(x, maps, f, *, streams: int, clamp: Optional[float]):
    """The streams after a sublayer, clip(res x + post^T f, +-clamp) (no clip
    where `clamp` is None) in x's dtype: x [B, S, n d] and the maps as
    mhc_pre returned them, f [B, S, d] the sublayer's output."""
    _M_FUSED.inc(op="post")
    return _post(x, maps, f, _Mix(streams, 0.0, 0, clamp, _interpret()))
