"""Distributed correctness: burst attention on a simulated 8-device mesh vs
the full-sequence dense oracle — the reference's integration test
(test/test_burst.py:159-219) without hardware, run in float32 so the ring
math is validated tightly, across layouts x causal x ring topology x GQA x
backward-comm mode."""

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh
import pytest

from burst_attn_tpu import BurstConfig, burst_attn
from burst_attn_tpu.ops.reference import dense_attention
from burst_attn_tpu.parallel import layouts
from burst_attn_tpu.utils.testing import check_close, random_qkv

KEY = jax.random.PRNGKey(7)


def make_mesh(shape):
    devs = np.array(jax.devices()[: int(np.prod(shape))]).reshape(shape)
    names = ("sp",) if len(shape) == 1 else ("inter", "intra")
    return Mesh(devs, names), names


def run_case(mesh_shape, layout, causal, kv_heads=4, optimize_bwd_comm=True,
             seq_per_dev=16, backend="jnp", n=4, d=16, n_segments=None,
             window=None, d_v=None, **burst_kw):
    W = int(np.prod(mesh_shape))
    b = 1
    S = seq_per_dev * W
    mesh, names = make_mesh(mesh_shape)
    q, k, v, do = random_qkv(KEY, b, n, S, d, kv_heads=kv_heads,
                             dtype=jnp.float32, d_v=d_v)

    seg = None
    if n_segments:
        # monotone packed-document ids with boundaries off any shard edge
        cuts = jnp.sort(jax.random.randint(
            jax.random.PRNGKey(11), (b, n_segments - 1), 1, S))
        seg = jnp.sum(jnp.arange(S)[None, :, None] >= cuts[:, None, :],
                      axis=-1).astype(jnp.int32)

    # one program a side (value and gradients together): what these cases
    # cost is compilation, not arithmetic

    # oracle on natural token order
    def ref_loss(q, k, v):
        o = dense_attention(q, k, v, causal=causal, window=window,
                            segment_ids=seg)
        return jnp.sum(o.astype(jnp.float32) * do), o

    (_, o_ref), (dq_ref, dk_ref, dv_ref) = jax.jit(jax.value_and_grad(
        ref_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)

    # burst on layout order
    ql, kl, vl, dol = (layouts.to_layout(t, layout, W, 2) for t in (q, k, v, do))
    segl = None if seg is None else layouts.to_layout(seg, layout, W, 1)

    def burst_loss(ql, kl, vl):
        o = burst_attn(
            ql, kl, vl, mesh=mesh, seq_axes=names, causal=causal, layout=layout,
            backend=backend, optimize_bwd_comm=optimize_bwd_comm,
            segment_ids=segl, window=window, **burst_kw,
        )
        return jnp.sum(o.astype(jnp.float32) * dol), o

    (_, o_l), (dq_l, dk_l, dv_l) = jax.jit(jax.value_and_grad(
        burst_loss, argnums=(0, 1, 2), has_aux=True))(ql, kl, vl)

    o = layouts.from_layout(o_l, layout, W, 2)
    dq = layouts.from_layout(dq_l, layout, W, 2)
    dk = layouts.from_layout(dk_l, layout, W, 2)
    dv = layouts.from_layout(dv_l, layout, W, 2)

    tag = f"mesh={mesh_shape} layout={layout} causal={causal} kvh={kv_heads}"
    check_close(o, o_ref, rtol=2e-4, atol=2e-4, msg=f"o {tag}")
    check_close(dv, dv_ref, rtol=2e-4, atol=2e-4, msg=f"dv {tag}")
    check_close(dk, dk_ref, rtol=2e-4, atol=2e-4, msg=f"dk {tag}")
    check_close(dq, dq_ref, rtol=2e-4, atol=2e-4, msg=f"dq {tag}")


@pytest.mark.parametrize("mesh_shape", [(8,), (2, 4)])
def test_noncausal(mesh_shape):
    run_case(mesh_shape, "contig", causal=False)


@pytest.mark.parametrize("layout", ["contig", "zigzag", "striped"])
def test_causal_single_ring(layout):
    run_case((8,), layout, causal=True)


@pytest.mark.parametrize("layout", ["zigzag", "striped"])
@pytest.mark.parametrize("mesh_shape", [(2, 4), (4, 2)])
def test_causal_double_ring(layout, mesh_shape):
    run_case(mesh_shape, layout, causal=True)


@pytest.mark.parametrize("kv_heads", [1, 2])
def test_gqa(kv_heads):
    run_case((2, 4), "zigzag", causal=True, kv_heads=kv_heads)


def test_unoptimized_bwd_comm():
    run_case((2, 4), "zigzag", causal=True, optimize_bwd_comm=False)


def test_small_world_2():
    run_case((2,), "zigzag", causal=True)


def test_unknown_backend_names_the_valid_ones():
    """One ring, two tiles: any other backend string (the fused RDMA ring's
    among them, which left with PR 31) is refused by name."""
    mesh, names = make_mesh((2,))
    q = jnp.zeros((1, 2, 32, 8), jnp.float32)
    with pytest.raises(ValueError, match="'jnp' or 'pallas'"):
        BurstConfig(backend="fused_ring")
    with pytest.raises(ValueError, match="'jnp' or 'pallas'"):
        burst_attn(q, q, q, mesh=mesh, seq_axes=names, backend="fused_ring")


def test_pallas_backend_in_ring_interpret():
    """The pallas tile inside the distributed ring (interpret mode off-TPU):
    closes the gap between 'kernels correct standalone' (test_pallas.py) and
    'kernels correct as the ring's tile' — catches contract drift in the
    carry-in state or MaskSpec plumbing between burst.py and the kernels."""
    run_case((4,), "zigzag", causal=True, kv_heads=2, n=2,
             backend="pallas", block_q=16, block_kv=16)


def test_pallas_striped_triangular_in_ring_interpret():
    """Striped causal rounds route through the triangular-grid kernels
    (burst.py case split) — exercise that path inside the ring.
    seq_per_dev=32 with 16-wide blocks gives nqb=2 per shard, satisfying
    the tri gates (nqb even, >= 2) so the wrapped-diagonal grid actually
    runs (kv_heads == n so the bwd group=1 gate holds too)."""
    run_case((4,), "striped", causal=True, kv_heads=2, n=2, seq_per_dev=32,
             backend="pallas", block_q=16, block_kv=16)


@pytest.mark.parametrize("layout", ["zigzag", "striped"])
def test_uniform_spec_path_no_case_split(layout):
    """case_split=False keeps the single uniform masked tile per round
    (the original scheduling) — both schedulings must match the oracle."""
    run_case((2, 4), layout, causal=True, case_split=False)


@pytest.mark.parametrize("mesh_shape", [(8,), (2, 4)])
def test_cross_attention_lengths(mesh_shape):
    """Encoder-decoder shape: q and kv with DIFFERENT sequence lengths,
    both sharded over the ring (non-causal — the rectangular MaskSpec
    already covers s_q != s_kv round tiles).  fwd + grads vs the dense
    oracle."""
    W = int(np.prod(mesh_shape))
    mesh, names = make_mesh(mesh_shape)
    sq, skv = 16 * W, 32 * W
    ks = jax.random.split(jax.random.PRNGKey(17), 4)
    q = jax.random.normal(ks[0], (1, 4, sq, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, skv, 16), jnp.float32)  # GQA too
    v = jax.random.normal(ks[2], (1, 2, skv, 16), jnp.float32)
    do = jax.random.normal(ks[3], (1, 4, sq, 16), jnp.float32)

    def ref_loss(q, k, v):
        o = dense_attention(q, k, v)
        return jnp.sum(o.astype(jnp.float32) * do), o

    (_, o_ref), g_ref = jax.jit(jax.value_and_grad(
        ref_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)

    def burst_loss(q, k, v):
        o = burst_attn(q, k, v, mesh=mesh, seq_axes=names, causal=False,
                       layout="contig", backend="jnp")
        return jnp.sum(o.astype(jnp.float32) * do), o

    (_, o), g = jax.jit(jax.value_and_grad(
        burst_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    check_close(o, o_ref, rtol=2e-4, atol=2e-4, msg="cross o")
    for got, want, nm in zip(g, g_ref, "qkv"):
        check_close(got, want, rtol=2e-4, atol=2e-4, msg=f"cross d{nm}")

    # causal cross-lengths are undefined (diagonal alignment) — loud error
    # instead of a silently-misaligned forward + bwd shape crash
    with pytest.raises(Exception, match="cross-attention"):
        jax.block_until_ready(burst_attn(
            q, k, v, mesh=mesh, seq_axes=names, causal=True, layout="zigzag",
            backend="jnp"))


@pytest.mark.parametrize("layout", ["contig", "zigzag", "striped"])
def test_segments_single_ring(layout):
    """Packed sequences in the distributed ring: kv-side ids ride the KV
    rotation, q-side ids rotate with the backward payload; boundaries land
    mid-shard on an 8-way ring."""
    run_case((8,), layout, causal=True, n_segments=3)


def test_segments_double_ring_gqa():
    run_case((2, 4), "zigzag", causal=True, kv_heads=2, n_segments=4)


def test_segments_noncausal():
    run_case((8,), "contig", causal=False, n_segments=3)


def test_segments_no_case_split():
    run_case((2, 4), "zigzag", causal=True, n_segments=3, case_split=False)


def test_bf16_reference_tolerance():
    """bf16 end-to-end within the reference's own tolerance convention
    (rtol 1e-3 / atol 1e-2 in half precision, test/checker.py:10)."""
    W, b, n, d = 8, 1, 2, 32
    S = 32 * W
    mesh, names = make_mesh((8,))
    q, k, v, _ = random_qkv(KEY, b, n, S, d, dtype=jnp.bfloat16)
    o_ref = dense_attention(q, k, v, causal=True)
    ql, kl, vl = (layouts.to_layout(t, "zigzag", W, 2) for t in (q, k, v))
    o_l = burst_attn(
        ql, kl, vl, mesh=mesh, seq_axes=names, causal=True, layout="zigzag", backend="jnp"
    )
    o = layouts.from_layout(o_l, "zigzag", W, 2)
    check_close(o, o_ref, rtol=4e-2, atol=4e-2, msg="bf16 o")


def _sweep_cases():
    """Randomized ring-level interaction sweep: mesh topology x layout x
    causal x GQA x window x packed segments x backend x bwd-comm mode vs
    the dense oracle — the targeted tests each pin one dimension; this
    guards combinations (e.g. double-ring striped GQA on the pallas
    backend, or windowed contig with packed segments), plus pinned
    configs for pairs the seed might miss."""
    rng = np.random.RandomState(41)
    cases = []
    for _ in range(7):
        layout = ["zigzag", "striped", "contig"][int(rng.randint(3))]
        causal = bool(rng.rand() < 0.75)
        wnd = (int(rng.choice([24, 48]))
               if (layout == "contig" and causal and rng.rand() < 0.4)
               else None)
        cases.append(dict(
            mesh_shape=[(8,), (2, 4), (4, 2)][int(rng.randint(3))],
            layout=layout, causal=causal,
            kv_heads=int(rng.choice([2, 4])),
            optimize_bwd_comm=bool(rng.rand() < 0.5),
            n_segments=int(rng.choice([0, 3])) or None,
            window=wnd))
    cases += [
        # pinned: double-ring striped GQA on pallas-interpret; windowed
        # contig + segments on a double ring; zigzag packed GQA no-opt-comm
        dict(mesh_shape=(2, 4), layout="striped", causal=True, kv_heads=2,
             backend="pallas", window=None, n_segments=None),
        dict(mesh_shape=(2, 4), layout="contig", causal=True, kv_heads=4,
             window=24, n_segments=3),
        dict(mesh_shape=(8,), layout="zigzag", causal=True, kv_heads=2,
             optimize_bwd_comm=False, n_segments=4, window=None),
    ]
    seen = {"wnd_seg": 0, "double_ring": 0, "gqa_striped": 0}
    for c in cases:
        if c.get("window") and c.get("n_segments"):
            seen["wnd_seg"] += 1
        if len(c["mesh_shape"]) == 2:
            seen["double_ring"] += 1
        if c["layout"] == "striped" and c["kv_heads"] < 4:
            seen["gqa_striped"] += 1
    assert (seen["wnd_seg"] >= 1 and seen["double_ring"] >= 2
            and seen["gqa_striped"] >= 1), seen
    return cases


@pytest.mark.parametrize("case", _sweep_cases(), ids=lambda c: "-".join(
    f"{v}" for v in c.values()).replace(" ", ""))
def test_ring_random_config_property_sweep(case):
    run_case(**case)
