"""burstlint mutation suite: every rule must FIRE on a seeded defect with
the right file:line, and stay QUIET on the real (fixed) codebase.

The jaxpr-family mutations build deliberately-wrong ring shard programs
(reversed rotation, dq that never returns home, swapped-pair permutation,
un-truncated windowed ring, bf16 accumulator, downcast lse) and feed them
through the same verifiers the CLI runs on the real entry points; the AST
mutations are fixture files written to tmp_path.
"""

import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from burst_attn_tpu.analysis import astlint, numerics, oracle, ringcheck
from burst_attn_tpu.analysis.core import RULES, run_analysis
from burst_attn_tpu.parallel.ring import ppermute_by

ANCHOR = ("seeded.py", 7)


def _mesh4():
    return Mesh(np.asarray(jax.devices()[:4]), ("sp",))


def _rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# registry / clean-run


def test_at_least_8_rules_registered():
    from burst_attn_tpu.analysis import astlint, costcheck, numerics, \
        obscheck, poolcheck, protocheck, ringcheck, servecheck  # noqa: F401

    assert len(RULES) >= 8
    for expected in ("silent-except", "mesh-shape-index",
                     "host-transfer-in-jit", "time-in-jit",
                     "traced-bool-branch", "ring-rotation", "ring-hops",
                     "ring-order", "dq-return-home", "window-truncation",
                     "fp32-accum", "lse-fp32",
                     "fused-ring-schedule",
                     "obs-jit-safe", "ckpt-jit-safe",
                     "pipe-fused-pure", "pipe-tick-identity",
                     "ragged-serve-safe", "pagepool-cow-safe",
                     "proto-transfer-atomic", "proto-journal-durable",
                     "proto-pool-conserved", "proto-no-deadlock",
                     "kernel-vmem-budget", "cost-model-consistent",
                     "tuning-table-sound"):
        assert expected in RULES, expected


def test_clean_run_on_real_package():
    findings = run_analysis()
    assert findings == [], "\n".join(f.format() for f in findings)


def test_oracle_proves_itself():
    for ni, na, rl in [(1, 4, None), (2, 4, None), (1, 8, 3)]:
        oracle.verify_dq_returns_home(ni, na, rl)
    # a tampered stream must NOT prove: live set that isn't a prefix
    assert oracle.live_rounds_contig(64, 4, 20) == {0, 1, 2}


# ---------------------------------------------------------------------------
# jaxpr mutations — ring family


def _trace_fwd_ring(hops_per_round):
    """A fwd-like shard program: rotate a 2-leaf kv payload by the given
    hop sizes (the healthy flat-4 ring is [1, 1, 1])."""
    mesh = _mesh4()

    def f(k, v):
        kv = (k, v)
        for h in hops_per_round:
            kv = ppermute_by(kv, "sp", h)
        return kv[0]

    spec = P(None, None, "sp", None)
    S = jax.ShapeDtypeStruct
    q = S((1, 2, 64, 8), jnp.bfloat16)
    fn = shard_map(f, mesh=mesh, in_specs=(spec, spec), out_specs=spec,
                   check_vma=False)
    return jax.make_jaxpr(fn)(q, q)


def _verify_fwd(jx, **kw):
    args = dict(kind="fwd", n_inter=1, n_intra=4, leaves_pay=2,
                axis_map={"sp": "intra"}, where="seeded fwd", anchor=ANCHOR)
    args.update(kw)
    return ringcheck.verify_traced_ring(jx, **args)


def test_healthy_ring_is_quiet():
    assert _verify_fwd(_trace_fwd_ring([1, 1, 1])) == []


def test_reversed_ring_permutation_fires():
    # rank i -> i-1: the ring spins against the schedule
    findings = _verify_fwd(_trace_fwd_ring([-1, -1, -1]))
    assert "ring-order" in _rules_of(findings)
    assert "ring-hops" in _rules_of(findings)
    assert findings[0].file == "seeded.py" and findings[0].line == 7


def test_extra_round_fires_hop_count():
    findings = _verify_fwd(_trace_fwd_ring([1, 1, 1, 1]))
    assert "ring-hops" in _rules_of(findings)


def test_swapped_pair_permutation_fires_rotation():
    mesh = _mesh4()

    def f(x):
        return jax.lax.ppermute(x, "sp", [(0, 1), (1, 0), (2, 3), (3, 2)])

    fn = shard_map(f, mesh=mesh, in_specs=P("sp"), out_specs=P("sp"),
                   check_vma=False)
    jx = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((4, 8), jnp.bfloat16))
    findings = _verify_fwd(jx, leaves_pay=1)
    assert "ring-rotation" in _rules_of(findings)


def _trace_bwd_ring(return_home):
    """A bwd-like shard program with the 4-leaf payload and the f32 rank-4
    dq accumulator of the real backward; `return_home=False` seeds the
    defect — dq's final hop home is dropped."""
    mesh = _mesh4()

    def f(q, do, lse):
        delta = lse
        pay = (delta, do, q, lse)
        dq = jnp.zeros(q.shape, jnp.float32)
        pay = ppermute_by(pay, "sp", 1)         # jump (h=1 on a full ring)
        for _ in range(2):                      # middle rounds
            pay = ppermute_by(pay, "sp", 1)
            dq = ppermute_by(dq, "sp", 1)
        dq = ppermute_by(dq, "sp", 1)           # last round rotation
        if return_home:
            dq = ppermute_by(dq, "sp", 1)       # final return-home hop
        return dq

    spec4 = P(None, None, "sp", None)
    spec3 = P(None, None, "sp")
    S = jax.ShapeDtypeStruct
    q = S((1, 2, 64, 8), jnp.bfloat16)
    lse = S((1, 2, 64), jnp.float32)
    fn = shard_map(f, mesh=mesh, in_specs=(spec4, spec4, spec3),
                   out_specs=spec4, check_vma=False)
    return jax.make_jaxpr(fn)(q, q, lse)


def _verify_bwd(jx, **kw):
    args = dict(kind="bwd", n_inter=1, n_intra=4, leaves_pay=4,
                axis_map={"sp": "intra"}, where="seeded bwd", anchor=ANCHOR)
    args.update(kw)
    return ringcheck.verify_traced_ring(jx, **args)


def test_healthy_bwd_ring_is_quiet():
    assert _verify_bwd(_trace_bwd_ring(return_home=True)) == []


def test_dq_not_returning_home_fires():
    findings = _verify_bwd(_trace_bwd_ring(return_home=False))
    assert "dq-return-home" in _rules_of(findings)
    assert any(f.file == "seeded.py" and f.line == 7 for f in findings)


def test_untruncated_window_ring_fires():
    # band oracle proves 3 live rounds (seq=64, world=4, window=20) but the
    # seeded ring still rotates the full n-1 = 3 hops
    live = oracle.live_rounds_contig(64, 4, 20)
    assert live == {0, 1, 2}
    findings = _verify_fwd(_trace_fwd_ring([1, 1, 1]), r_live=len(live),
                           window=True)
    assert "window-truncation" in _rules_of(findings)


def test_truncated_window_ring_is_quiet():
    findings = _verify_fwd(_trace_fwd_ring([1, 1]), r_live=3, window=True)
    assert "window-truncation" not in _rules_of(findings)
    assert findings == []


# ---------------------------------------------------------------------------
# jaxpr mutations — numerics family


def test_bf16_accumulator_fires():
    S = jax.ShapeDtypeStruct
    q = S((1, 2, 64, 16), jnp.bfloat16)

    def bad(q, k):  # bf16 dot WITHOUT a f32 accumulator
        return jax.lax.dot_general(q[0, 0], k[0, 0], (((1,), (1,)), ((), ())))

    jx = jax.make_jaxpr(bad)(q, q)
    findings = numerics.check_trace(jx, where="seeded", anchor=ANCHOR)
    assert _rules_of(findings) == {"fp32-accum"}
    assert findings[0].file == "seeded.py" and findings[0].line == 7


def test_f32_accumulator_is_quiet():
    S = jax.ShapeDtypeStruct
    q = S((1, 2, 64, 16), jnp.bfloat16)

    def good(q, k):
        return jax.lax.dot_general(q[0, 0], k[0, 0], (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    jx = jax.make_jaxpr(good)(q, q)
    assert numerics.check_trace(jx, where="seeded", anchor=ANCHOR) == []


def test_lse_downcast_fires():
    S = jax.ShapeDtypeStruct
    lse = S((1, 2, 64), jnp.float32)
    jx = jax.make_jaxpr(lambda lse: lse.astype(jnp.bfloat16) * 1)(lse)
    findings = numerics.check_trace(jx, where="seeded", anchor=ANCHOR)
    assert _rules_of(findings) == {"lse-fp32"}


# ---------------------------------------------------------------------------
# AST mutations


def _lint_fixture(tmp_path, source):
    p = tmp_path / "fixture.py"
    p.write_text(textwrap.dedent(source))
    return astlint.lint_file(str(p))


def test_bare_except_pass_fires(tmp_path):
    findings = _lint_fixture(tmp_path, """\
        def f():
            try:
                g()
            except Exception:
                pass
    """)
    assert [(f.rule, f.line) for f in findings] == [("silent-except", 4)]


def test_narrow_except_pass_is_flow_control(tmp_path):
    findings = _lint_fixture(tmp_path, """\
        def f(it):
            try:
                next(it)
            except StopIteration:
                pass
    """)
    assert findings == []


def test_mesh_shape_index_fires(tmp_path):
    findings = _lint_fixture(tmp_path, """\
        def f(mesh, axes):
            return [mesh.shape[a] for a in axes]
    """)
    assert [(f.rule, f.line) for f in findings] == [("mesh-shape-index", 2)]


def test_mesh_shape_get_is_quiet(tmp_path):
    findings = _lint_fixture(tmp_path, """\
        def f(mesh, axes):
            return [mesh.shape.get(a, 1) for a in axes]
    """)
    assert findings == []


def test_host_transfer_and_time_and_branch_fire(tmp_path):
    findings = _lint_fixture(tmp_path, """\
        import time
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            a = x.item()
            b = jax.device_get(x)
            c = float(jnp.sum(x))
            t = time.time()
            if jnp.sum(x) > 0:
                return a
            return b
    """)
    got = sorted((f.rule, f.line) for f in findings)
    assert got == [
        ("host-transfer-in-jit", 7),
        ("host-transfer-in-jit", 8),
        ("host-transfer-in-jit", 9),
        ("time-in-jit", 10),
        ("traced-bool-branch", 11),
    ]


def test_host_code_outside_jit_is_quiet(tmp_path):
    findings = _lint_fixture(tmp_path, """\
        import time
        import jax.numpy as jnp

        def host_loop(x):
            t = time.time()
            v = float(jnp.sum(x))
            if jnp.sum(x) > 0:
                return v
            return t
    """)
    assert findings == []


def test_jit_context_through_wrapper_reference(tmp_path):
    # f is never decorated but is passed to lax.scan — still a jit context
    findings = _lint_fixture(tmp_path, """\
        import time
        from jax import lax

        def body(carry, x):
            t = time.time()
            return carry, x

        def run(xs):
            return lax.scan(body, 0, xs)
    """)
    assert [(f.rule, f.line) for f in findings] == [("time-in-jit", 5)]


def test_suppression_comment_silences(tmp_path):
    findings = _lint_fixture(tmp_path, """\
        def f(mesh, a):
            return mesh.shape[a]  # burstlint: disable=mesh-shape-index
    """)
    assert findings == []


def test_zero_suppressions_in_package():
    """The codebase carries ZERO burstlint suppression comments (ISSUE 4:
    the last one — dist_decode's prefill epilogue — was retired by indexing
    with the host numpy scalar directly instead of int()-coercing it)."""
    import os

    import burst_attn_tpu
    from burst_attn_tpu.analysis.core import suppressed_rules

    root = os.path.dirname(burst_attn_tpu.__file__)
    carried = []
    for p in astlint.default_paths(root):
        with open(p, encoding="utf-8") as f:
            for i, line in enumerate(f, 1):
                for r in suppressed_rules(line):
                    if r in RULES:  # docstrings show RULE placeholders
                        carried.append((os.path.relpath(p, root), i, r))
    assert carried == [], carried


# ---------------------------------------------------------------------------
# obs-jit-safe mutations (AST + jaxpr)


def test_obs_call_in_jit_fires(tmp_path):
    findings = _lint_fixture(tmp_path, """\
        import jax
        from burst_attn_tpu import obs

        _C = obs.counter("c")

        @jax.jit
        def f(x):
            obs.counter("steps").inc()
            _C.inc()
            with obs.span("s"):
                x = x + 1
            return x
    """)
    got = sorted((f.rule, f.line) for f in findings
                 if f.rule == "obs-jit-safe")
    assert got == [("obs-jit-safe", 8), ("obs-jit-safe", 9),
                   ("obs-jit-safe", 10)], [f.format() for f in findings]


def test_obs_import_spellings_all_tracked(tmp_path):
    # relative import, aliased import, and a submodule import all bind
    findings = _lint_fixture(tmp_path, """\
        import jax
        from burst_attn_tpu.obs.spans import span as mark
        import burst_attn_tpu.obs as o

        @jax.jit
        def f(x):
            with mark("inner"):
                o.gauge("g").set(1.0)
            return x
    """)
    got = sorted(f.line for f in findings if f.rule == "obs-jit-safe")
    assert got == [7, 8], [f.format() for f in findings]


def test_obs_host_boundary_is_quiet(tmp_path):
    findings = _lint_fixture(tmp_path, """\
        import jax
        from burst_attn_tpu import obs

        @jax.jit
        def step(x):
            return x + 1

        def dispatch(x):
            obs.counter("dispatch").inc()
            with obs.span("dispatch"):
                return step(x)
    """)
    assert [f for f in findings if f.rule == "obs-jit-safe"] == []


def test_obs_callback_prim_fires():
    from burst_attn_tpu.analysis import obscheck

    def bad(x):
        jax.debug.callback(lambda v: None, x)
        return x * 2

    jx = jax.make_jaxpr(bad)(jnp.ones(4))
    findings = obscheck.check_trace(jx, where="seeded", anchor=ANCHOR)
    assert _rules_of(findings) == {"obs-jit-safe"}
    assert findings[0].file == "seeded.py" and findings[0].line == 7


def test_obs_pure_callback_prim_fires():
    from burst_attn_tpu.analysis import obscheck

    def bad(x):
        return jax.pure_callback(
            lambda v: np.asarray(v), jax.ShapeDtypeStruct((4,), jnp.float32),
            x)

    jx = jax.make_jaxpr(bad)(jnp.ones(4, jnp.float32))
    findings = obscheck.check_trace(jx, where="seeded", anchor=ANCHOR)
    assert _rules_of(findings) == {"obs-jit-safe"}


def test_obs_clean_trace_is_quiet():
    from burst_attn_tpu.analysis import obscheck

    jx = jax.make_jaxpr(lambda x: x * 2)(jnp.ones(4))
    assert obscheck.check_trace(jx, where="seeded", anchor=ANCHOR) == []


def test_obs_devstats_exempt_from_ast_rule(tmp_path):
    """obs.devstats is the deliberately in-jit half of obs: every import
    spelling of it stays OUT of the obs-jit-safe binding set (its purity is
    proved by the jaxpr devstats-pure rule instead), while sibling obs
    imports in the same module keep firing."""
    findings = _lint_fixture(tmp_path, """\
        import jax
        from burst_attn_tpu.obs import devstats
        from burst_attn_tpu.obs.devstats import ring_stats
        from burst_attn_tpu import obs

        @jax.jit
        def f(x):
            st = devstats.ring_stats(1, 1, x.sum(), 1.0, 8, x, x, x)
            y = ring_stats(1, 1, x.sum(), 1.0, 8, x, x, x)
            obs.counter("bad").inc()
            return x
    """)
    got = [(f.rule, f.line) for f in findings if f.rule == "obs-jit-safe"]
    assert got == [("obs-jit-safe", 10)], [f.format() for f in findings]


def test_obs_trace_api_in_jit_fires(tmp_path):
    """The obs.trace request-tracing API is under the same jit-safety
    contract as registry/spans: direct submodule calls AND module-level
    aliases of the submodule or its functions must fire under jit."""
    findings = _lint_fixture(tmp_path, """\
        import jax
        from burst_attn_tpu.obs import trace as tracing

        T = tracing
        _rec = tracing.record_span

        @jax.jit
        def f(x, tc):
            tracing.record_span(tc, "p", 0.0, 1.0)
            T.marker(tc, "m", 0.0)
            _rec(tc, "q", 0.0, 1.0)
            return x
    """)
    got = sorted((f.rule, f.line) for f in findings
                 if f.rule == "obs-jit-safe")
    assert got == [("obs-jit-safe", 9), ("obs-jit-safe", 10),
                   ("obs-jit-safe", 11)], [f.format() for f in findings]


def test_obs_trace_host_boundary_is_quiet(tmp_path):
    """The sanctioned pattern — trace calls at the host dispatch
    boundary around a jit-compiled step — stays clean, and an alias of
    a NON-obs module does not poison the binding set."""
    findings = _lint_fixture(tmp_path, """\
        import json
        import jax
        from burst_attn_tpu.obs import trace as tracing

        J = json

        @jax.jit
        def step(x):
            return x + 1

        def dispatch(x, tc):
            with tracing.span(tc, "dispatch"):
                y = step(x)
            J.dumps({})
            return y
    """)
    assert [f for f in findings if f.rule == "obs-jit-safe"] == []


# ---------------------------------------------------------------------------
# devstats-pure mutations (jaxpr)


def test_devstats_callback_prim_fires_under_rule_name():
    """A callback smuggled into the stats-enabled trace is reported under
    the devstats-pure rule (same detector, different contract)."""
    from burst_attn_tpu.analysis import obscheck

    def bad(x):
        jax.debug.callback(lambda v: None, x)
        return x * 2

    jx = jax.make_jaxpr(bad)(jnp.ones(4))
    findings = obscheck.check_trace(jx, where="seeded stats fwd",
                                    anchor=ANCHOR,
                                    rule_name="devstats-pure")
    assert _rules_of(findings) == {"devstats-pure"}
    assert findings[0].file == "seeded.py" and findings[0].line == 7


def test_devstats_off_identity_divergence_fires():
    """Different stats-off vs plain programs == devstats machinery leaking
    into the off path -> devstats-pure fires; identical programs (even when
    their pretty-print differs only by heap addresses of embedded function
    objects) stay quiet."""
    from burst_attn_tpu.analysis import obscheck

    j_plain = jax.make_jaxpr(lambda x: x * 2)(jnp.ones(4))
    j_leaky = jax.make_jaxpr(lambda x: x * 2 + 1)(jnp.ones(4))
    findings = obscheck.check_off_identity(j_leaky, j_plain, anchor=ANCHOR)
    assert _rules_of(findings) == {"devstats-pure"}

    j_same = jax.make_jaxpr(lambda x: x * 2)(jnp.ones(4))
    assert obscheck.check_off_identity(j_same, j_plain, anchor=ANCHOR) == []
    # the address canonicalizer: identical programs whose reprs differ only
    # by 0x... heap addresses must compare equal
    assert (obscheck._canon_jaxpr("f at 0x7f00aa") ==
            obscheck._canon_jaxpr("f at 0x7f11bb"))


# ---------------------------------------------------------------------------
# ckpt-jit-safe mutations (jaxpr)


def _tiny_serve_trace(hook=None):
    """Trace a ragged serve step, optionally smuggling a 'journal write'
    callback INTO the compiled program (the defect ckpt-jit-safe exists to
    catch: durability hooks belong in the engine's host loop)."""
    from burst_attn_tpu.models.paged_decode import init_paged_state
    from burst_attn_tpu.models.transformer import ModelConfig, init_params
    from burst_attn_tpu.serving.model import ragged_model_step

    cfg = ModelConfig(vocab=31, d_model=16, n_layers=1, n_heads=2,
                      n_kv_heads=1, d_head=8, d_ff=32, attn_backend="jnp",
                      remat=False, dtype=jnp.float32, batch_axis=None,
                      head_axis=None)
    params = init_params(jax.random.PRNGKey(0), cfg)
    state, _ = init_paged_state(cfg, slots=2, n_pages=4, page=128,
                                max_pages_per_seq=2)

    def step(p, t, ql, st):
        logits, st = ragged_model_step(p, t, ql, st, cfg, attn="dense")
        if hook is not None:
            hook(logits)
        return logits, st

    return jax.make_jaxpr(step)(params, jnp.zeros((2, 8), jnp.int32),
                                jnp.ones((2,), jnp.int32), state)


def test_ckpt_journal_callback_in_step_fires():
    """A journal append spelled as jax.debug.callback inside the serve
    step is exactly the smuggled durability hook ckpt-jit-safe bans."""
    from burst_attn_tpu.analysis import obscheck

    jx = _tiny_serve_trace(
        hook=lambda logits: jax.debug.callback(lambda v: None, logits))
    findings = obscheck.check_trace(jx, where="seeded serve step",
                                    anchor=ANCHOR,
                                    rule_name="ckpt-jit-safe")
    assert _rules_of(findings) == {"ckpt-jit-safe"}
    assert findings[0].file == "seeded.py" and findings[0].line == 7


def test_ckpt_real_serve_step_is_quiet():
    """The real serve step (journal hooks live in the host loop) traces
    callback-free."""
    from burst_attn_tpu.analysis import obscheck

    jx = _tiny_serve_trace()
    assert obscheck.check_trace(jx, where="serve step", anchor=ANCHOR,
                                rule_name="ckpt-jit-safe") == []


# ---------------------------------------------------------------------------
# pipe-fused-pure / pipe-tick-identity mutations (jaxpr, ISSUE 20)


def _tiny_multi_step_trace(hook=None):
    """Trace a fused multi-step decode scan, optionally smuggling a
    primitive into the scan body via `hook(choice)`."""
    from burst_attn_tpu.models.paged_decode import init_paged_state
    from burst_attn_tpu.models.transformer import ModelConfig, init_params
    from burst_attn_tpu.serving import model as serving_model

    cfg = ModelConfig(vocab=31, d_model=16, n_layers=1, n_heads=2,
                      n_kv_heads=1, d_head=8, d_ff=32, attn_backend="jnp",
                      remat=False, dtype=jnp.float32, batch_axis=None,
                      head_axis=None)
    params = init_params(jax.random.PRNGKey(0), cfg)
    state, _ = init_paged_state(cfg, slots=2, n_pages=4, page=128,
                                max_pages_per_seq=2)
    first = jnp.zeros((2,), jnp.int32)
    qlens = jnp.ones((2,), jnp.int32)
    rng = jax.random.PRNGKey(1)

    def prog(p, t, ql, st, r):
        choices, st, r = serving_model.multi_step_decode(
            p, t, ql, st, r, cfg, k=3, attn="dense")
        if hook is not None:
            hook(choices)
        return choices, st, r

    return jax.make_jaxpr(prog)(params, first, qlens, state, rng)


def test_pipe_fused_callback_fires():
    """A per-step host hook inside the fused launch (a progress callback,
    a debug print) multiplies host round trips by K — pipe-fused-pure
    must flag it."""
    from burst_attn_tpu.analysis import obscheck

    jx = _tiny_multi_step_trace(
        hook=lambda c: jax.debug.callback(lambda v: None, c))
    findings = obscheck.check_trace(jx, where="seeded fused scan",
                                    anchor=ANCHOR,
                                    rule_name="pipe-fused-pure")
    assert _rules_of(findings) == {"pipe-fused-pure"}


def test_pipe_fused_remote_dma_fires():
    """A collective smuggled into the decode program is wire traffic per
    launch — check_remote_free must flag it even though it is not a
    callback."""
    from jax.sharding import Mesh, PartitionSpec as P

    from burst_attn_tpu.analysis import obscheck
    from jax import shard_map

    devs = jax.devices()[:2]
    mesh = Mesh(np.asarray(devs), ("sp",))
    prog = shard_map(lambda x: jax.lax.psum(x, "sp"), mesh=mesh,
                     in_specs=P("sp"), out_specs=P(), check_vma=False)
    jx = jax.make_jaxpr(prog)(jnp.zeros((2,), jnp.float32))
    findings = obscheck.check_remote_free(jx, where="seeded decode",
                                          anchor=ANCHOR)
    assert _rules_of(findings) == {"pipe-fused-pure"}
    assert "psum" in findings[0].message


def test_pipe_fused_real_scan_is_quiet():
    """The real fused multi-step scan carries neither callbacks nor
    remote/collective primitives."""
    from burst_attn_tpu.analysis import obscheck

    jx = _tiny_multi_step_trace()
    assert obscheck.check_trace(jx, where="fused scan", anchor=ANCHOR,
                                rule_name="pipe-fused-pure") == []
    assert obscheck.check_remote_free(jx, where="fused scan",
                                      anchor=ANCHOR) == []


def test_pipe_tick_identity_canon_detects_divergence():
    """The K=1 identity gate compares canonical jaxpr strings: identical
    programs pass, a program with one extra equation fails."""
    from burst_attn_tpu.analysis import obscheck

    def f(x):
        return x * 2.0

    def g(x):
        return x * 2.0 + 1.0

    a = jax.make_jaxpr(f)(jnp.zeros((2,), jnp.float32))
    b = jax.make_jaxpr(f)(jnp.zeros((2,), jnp.float32))
    c = jax.make_jaxpr(g)(jnp.zeros((2,), jnp.float32))
    assert obscheck._canon_jaxpr(a) == obscheck._canon_jaxpr(b)
    assert obscheck._canon_jaxpr(a) != obscheck._canon_jaxpr(c)


def test_cli_exits_zero_on_repo():
    import subprocess
    import sys

    r = subprocess.run(
        [sys.executable, "-m", "burst_attn_tpu.analysis", "--json"],
        capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stdout + r.stderr
    import json

    d = json.loads(r.stdout)
    assert len(d["rules_registered"]) >= 8
    assert d["n_findings"] == 0


# ---------------------------------------------------------------------------
# schedule-IR program proofs (ISSUE 6): the compiler's emitted programs are
# simulation-proven (ringcheck.verify_ring_programs); deliberately corrupted
# programs — flipped direction, shortened prefetch distance, aliased slot —
# must each fire, or the proof has no teeth


def _export(prog):
    return prog.export()


def test_ring_program_matrix_proves_clean():
    findings = ringcheck.verify_ring_programs()
    assert findings == [], "\n".join(f.format() for f in findings)


def test_ring_program_flipped_direction_fires():
    """Swapping a channel's direction (cw -> ccw) delivers the mirror
    rotation: every consume after round 0 holds the wrong partition."""
    from burst_attn_tpu.parallel import schedule

    prog = _export(schedule.compile_fwd("uni", 8))
    prog["channels"] = ("ccw",)
    with pytest.raises(AssertionError, match="rotation says"):
        oracle.verify_ring_program(prog)

    # and the bidi mirror: flip only the second channel
    prog = _export(schedule.compile_fwd("bidi", 8))
    prog["channels"] = ("cw", "cw")
    with pytest.raises(AssertionError, match="rotation says"):
        oracle.verify_ring_program(prog)


def test_ring_program_shortened_prefetch_fires():
    """Moving the double ring's inter hop to the cycle's LAST round keeps
    delivery intact but shrinks the prefetch distance below one intra
    cycle — the slow hop can no longer hide behind compute."""
    from burst_attn_tpu.parallel import schedule

    prog = _export(schedule.compile_bwd("double", 4, 2))
    rows = {k: list(v) for k, v in prog["rows"].items()}
    assert rows["send1"][0] == 1
    late = prog["n_intra"] - 1
    for col in ("send1", "src_slot1", "dst_slot1"):
        rows[col][late] = rows[col][0]
        rows[col][0] = 0
    prog["rows"] = {k: tuple(v) for k, v in rows.items()}
    with pytest.raises(AssertionError, match="prefetch distance"):
        oracle.verify_ring_program(prog)


def test_ring_program_aliased_slot_fires():
    """Aiming a send at the slot another round still has to read is the
    overwrite-before-read hazard the per-slot credits exist to prevent."""
    from burst_attn_tpu.parallel import schedule

    prog = _export(schedule.compile_fwd("uni", 8, slots=3))
    rows = {k: list(v) for k, v in prog["rows"].items()}
    rows["dst_slot0"][1] = rows["consume_slot"][1]  # round 2 reads it next
    prog["rows"] = {k: tuple(v) for k, v in rows.items()}
    with pytest.raises(AssertionError):
        oracle.verify_ring_program(prog)


def test_ring_program_dropped_home_hop_fires():
    """Turning a return-home hop into a plain ring hop strands the owner's
    gradient: the exactly-once home delivery proof must fire."""
    from burst_attn_tpu.parallel import schedule

    prog = _export(schedule.compile_bwd("uni", 8))
    rows = {k: list(v) for k, v in prog["rows"].items()}
    last = max(r for r in range(len(rows["dq_send"]))
               if rows["dq_send"][r] == schedule.DQ_HOME)
    rows["dq_send"][last] = schedule.DQ_NONE
    prog["rows"] = {k: tuple(v) for k, v in rows.items()}
    with pytest.raises(AssertionError, match="home"):
        oracle.verify_ring_program(prog)


# ---------------------------------------------------------------------------
# occupancy elision (ISSUE 11): elided ring programs are proven, undercut
# the dense remote-DMA census, and a broken elider is caught


def test_elided_ring_program_census_undercut():
    """Occupancy-truncated programs of every topology serve exactly the
    live prefix (oracle-proven) and strictly undercut the dense program's
    round count; the bidi topology also strictly undercuts the dense
    remote-DMA census (uni's census is call-site-bounded, so only <=)."""
    from burst_attn_tpu.parallel import schedule as sched

    world, r_live = 8, 3
    for topo, strict in (("uni", False), ("bidi", True)):
        for compiler, payload in ((sched.compile_fwd, 2),
                                  (sched.compile_bwd, 4)):
            prog = compiler(topo, world, r_live=r_live)
            dense = compiler(topo, world)
            oracle.verify_ring_program(prog.export(),
                                       live_deltas=tuple(range(r_live)))
            assert prog.n_rounds < dense.n_rounds, (topo, compiler.__name__)
            got = sched.expected_remote_dma(prog, payload)
            ref = sched.expected_remote_dma(dense, payload)
            assert got <= ref, (topo, compiler.__name__, got, ref)
            if strict:
                assert got < ref, (topo, compiler.__name__, got, ref)


def test_elision_mutation_fires_on_ring_programs():
    """Seeded-bad eliders are caught by the shared verify_elided_program
    obligation: a compiler that fails to elide (ships the dense program)
    keeps DEAD offsets; one that over-truncates drops LIVE offsets."""
    from burst_attn_tpu.parallel import schedule as sched

    world, r_live = 8, 3
    good = sched.compile_fwd("uni", world, r_live=r_live)
    assert ringcheck.verify_elided_program(good.export(), r_live,
                                           where="mutation") == []
    # mutation 1: no elision happened — the dense program claims r_live
    dense = sched.compile_fwd("uni", world)
    f1 = ringcheck.verify_elided_program(dense.export(), r_live,
                                         where="mutation")
    assert any(f.rule == "fused-ring-schedule" and "DEAD" in f.message
               for f in f1), [f.format() for f in f1]
    # mutation 2: over-eager elision dropped a live round
    over = sched.compile_fwd("uni", world, r_live=r_live - 1)
    f2 = ringcheck.verify_elided_program(over.export(), r_live,
                                         where="mutation")
    assert any(f.rule == "fused-ring-schedule" and "LIVE" in f.message
               for f in f2), [f.format() for f in f2]
    # same obligations hold for the backward compiler
    f3 = ringcheck.verify_elided_program(
        sched.compile_bwd("uni", world).export(), r_live, where="mutation")
    assert any("DEAD" in f.message for f in f3)


# ---------------------------------------------------------------------------
# wire-precision scale-handling proof (ISSUE 14): numerics.check_wire_trace
# proves every quantized send has a matching rescale before accumulation
# (reported under fp32-accum).  The mutations — a dropped rescale, a raw
# int8 MXU operand, a f16 accumulator smuggled behind the dequant, a bogus
# wire dtype in the schedule IR — must each fire, or the proof has no
# teeth.  The clean direction is ringcheck's run over the ring's forward
# and backward shard programs at wire_dtype int8 and fp8
# (verify_ring_entry, via test_clean_run_on_real_package).


S4 = jax.ShapeDtypeStruct((64, 16), jnp.float32)
S8 = jax.ShapeDtypeStruct((64, 16), jnp.int8)
SC = jax.ShapeDtypeStruct((), jnp.float32)


def test_wire_dropped_rescale_fires():
    """Dequantizing a wire payload and accumulating WITHOUT the per-block
    scale multiply is exactly the silent-corruption defect the proof
    exists to catch."""

    def bad(q, k8):
        k = k8.astype(jnp.float32)          # dequant, scale dropped
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return jnp.sum(s)                   # reduction eats the raw value

    jx = jax.make_jaxpr(bad)(S4, S8)
    findings = numerics.check_wire_trace(jx, where="seeded", anchor=ANCHOR)
    assert findings, "dropped rescale did not fire"
    assert _rules_of(findings) == {"fp32-accum"}
    assert any("rescale" in f.message for f in findings)
    assert findings[0].file == "seeded.py" and findings[0].line == 7


def test_wire_dropped_rescale_in_ring_fires(monkeypatch):
    """The same defect seeded in the ring itself: a dequantize that casts
    up and drops the scale must fire on the traced forward AND backward
    shard programs, on both wire dtypes."""
    from burst_attn_tpu.parallel import burst

    assert ringcheck.verify_ring_entry(ringcheck.ENTRIES[0]) == []
    monkeypatch.setattr(burst, "wire_dequantize",
                        lambda x8, scale, dtype: x8.astype(dtype))
    findings = ringcheck.verify_ring_entry(ringcheck.ENTRIES[0])
    assert _rules_of(findings) == {"fp32-accum"}
    for tag in ("fwd wire=int8", "bwd wire=int8", "fwd wire=fp8",
                "bwd wire=fp8"):
        assert any(tag in f.message for f in findings), tag


def test_wire_escaped_unscaled_output_fires():
    """An unscaled dequantized value flowing straight to the trace output
    (through taint-transparent reshapes) is also a dropped rescale."""
    jx = jax.make_jaxpr(
        lambda k8: k8.astype(jnp.float32).reshape(16, 64))(S8)
    findings = numerics.check_wire_trace(jx, where="seeded", anchor=ANCHOR)
    assert any("never met its scale" in f.message for f in findings), [
        f.format() for f in findings]


def test_wire_raw_quant_dot_fires():
    """A raw int8 operand into dot_general bypasses the cast-up-then-
    rescale contract entirely."""

    def bad(a8, b8):
        return jax.lax.dot_general(a8, b8, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.int32)

    jx = jax.make_jaxpr(bad)(S8, S8)
    findings = numerics.check_wire_trace(jx, where="seeded", anchor=ANCHOR)
    assert any("raw" in f.message and "int8" in f.message
               for f in findings), [f.format() for f in findings]


def test_wire_fp16_accum_behind_quant_fires():
    """A f16 accumulator smuggled BEHIND the dequant+rescale: the scale
    proof is satisfied (the mul is there) but the fp32-accum census of the
    same verifier must still fire — quantizing the wire never licenses a
    low-precision accumulator."""

    def bad(q, k8, sc):
        k = k8.astype(jnp.float16) * sc.astype(jnp.float16)
        return jax.lax.dot_general(q.astype(jnp.float16), k,
                                   (((1,), (1,)), ((), ())))

    jx = jax.make_jaxpr(bad)(S4, S8, SC)
    findings = numerics.check_trace(jx, where="seeded bwd", anchor=ANCHOR)
    assert "fp32-accum" in _rules_of(findings), [
        f.format() for f in findings]
    # and the rescale itself kept the scale proof quiet
    assert numerics.check_wire_trace(jx, where="seeded bwd",
                                     anchor=ANCHOR) == []


def test_wire_deferred_rescale_after_dot_is_quiet():
    """Cast up, dot, THEN fold the scalar scale into the score
    (distributivity) must stay quiet."""

    def good(q, k8, sc):
        k = k8.astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sc
        return jnp.sum(s)

    jx = jax.make_jaxpr(good)(S4, S8, SC)
    assert numerics.check_wire_trace(jx, where="seeded", anchor=ANCHOR) == []


def test_wire_program_bogus_dtype_fires():
    """The schedule-IR oracle validates the wire field: a program claiming
    an unknown wire dtype must not prove."""
    from burst_attn_tpu.parallel import schedule

    prog = _export(schedule.compile_fwd("uni", 8, wire="int8"))
    oracle.verify_ring_program(prog)  # the real one proves
    prog["wire"] = "int4"
    with pytest.raises(AssertionError, match="wire"):
        oracle.verify_ring_program(prog)


def test_wire_recompile_credit_neutral():
    """The wire recompile of every topology keeps the op table, slot
    banks, and copy-in list bit-identical to the dense compile (scale
    sub-payloads ride the SAME slot credits) while the remote-DMA census
    strictly grows."""
    from burst_attn_tpu.parallel import schedule as sched

    for topo, ni, na in (("uni", 1, 8), ("bidi", 1, 4), ("double", 2, 4)):
        for compiler, payload in ((sched.compile_fwd, 2),
                                  (sched.compile_bwd, 4)):
            dense = compiler(topo, na, ni)
            wired = compiler(topo, na, ni, wire="int8")
            assert np.array_equal(np.asarray(wired.to_table()),
                                  np.asarray(dense.to_table())), (
                topo, compiler.__name__)
            assert tuple(wired.slots) == tuple(dense.slots)
            assert list(wired.copy_in) == list(dense.copy_in)
            assert (sched.expected_remote_dma(wired, payload)
                    > sched.expected_remote_dma(dense, payload)), (
                topo, compiler.__name__)


# ---------------------------------------------------------------------------
# pagepool-cow-safe mutations (ISSUE 13): the prefix-cache write barrier.
# poolcheck drives a real tiny prefix-cache engine and checks every launch's
# scatter columns against the live allocator, then proves the pool drains;
# the mutations below seed exactly the two silent-corruption defects the
# rule exists to catch.  The clean run rides tier-1 via
# test_clean_run_on_real_package; the mutants are slow-marked (each spins
# up and serves the full sharing schedule).


def test_poolcheck_rule_registered():
    from burst_attn_tpu.analysis import poolcheck  # noqa: F401

    assert "pagepool-cow-safe" in RULES
    assert RULES["pagepool-cow-safe"].kind == "jaxpr"
    # the anchor must resolve into the live engine source, not <trace>
    path, line = poolcheck._anchor()
    assert path.endswith("engine.py") and line > 0


def test_poolcheck_skipped_cow_fires(monkeypatch):
    """A launch that scatters into a refcount>1 page (CoW barrier no-op'd)
    is silent cross-request corruption — the rule must see it."""
    from burst_attn_tpu.analysis import poolcheck
    from burst_attn_tpu.serving import engine as eng_mod

    monkeypatch.setattr(
        eng_mod, "cow_pages",
        lambda state, pool, slot, n, cache=None: (state, []))
    findings = poolcheck.check_all()
    assert "pagepool-cow-safe" in _rules_of(findings)
    assert any("shared page" in f.message and "refcount" in f.message
               for f in findings), [f.format() for f in findings]


def test_poolcheck_refcount_leak_fires(monkeypatch):
    """A release that decrements but never returns pages to the free list
    leaks the whole pool over time — the drain check must see it."""
    from burst_attn_tpu.analysis import poolcheck
    from burst_attn_tpu.models import paged_decode as pd

    def leaky(self, ids):
        for i in [int(j) for j in ids]:
            if 0 < i < self.n_pages and self._refs[i] > 0:
                self._refs[i] -= 1  # decremented but NEVER freed

    monkeypatch.setattr(pd.PagePool, "release", leaky)
    findings = poolcheck.check_all()
    assert "pagepool-cow-safe" in _rules_of(findings)
    assert any("leak" in f.message for f in findings), [
        f.format() for f in findings]


# ---------------------------------------------------------------------------
# pool-quant-safe drives the SAME sharing schedule on an fp8-native pool
# (ISSUE 17) and proves (page, scale) pair atomicity at both seams: the
# CoW copy and the jitted scatter.  Each mutation below splits exactly one
# seam; the clean run rides check_all via test_clean_run_on_real_package.


def test_pool_quant_rule_registered():
    from burst_attn_tpu.analysis import poolcheck  # noqa: F401

    assert "pool-quant-safe" in RULES
    assert RULES["pool-quant-safe"].kind == "jaxpr"
    path, line = poolcheck._quant_anchor()
    assert path.endswith("model.py") and line > 0


def test_pool_quant_cow_scale_split_fires(monkeypatch):
    """A CoW copy that privatizes the K/V page columns but NOT the scale
    columns leaves the private page dequantizing with a stranger's (or
    the init) scales — silent corruption the pair-copy check must see."""
    from burst_attn_tpu.analysis import poolcheck
    from burst_attn_tpu.serving import model as serve_model

    def split_copy(state, src, dst):
        k_pages = tuple(kp.at[dst].set(kp[src]) for kp in state.k_pages)
        v_pages = tuple(vp.at[dst].set(vp[src]) for vp in state.v_pages)
        return state._replace(k_pages=k_pages, v_pages=v_pages)

    monkeypatch.setattr(serve_model, "_copy_pages_jit", split_copy)
    findings = poolcheck._check_quant()
    assert _rules_of(findings) == {"pool-quant-safe"}
    assert any("pair split" in f.message and "not carried" in f.message
               for f in findings), [f.format() for f in findings]
    assert findings[0].file.endswith("model.py")


def test_pool_quant_scatter_scale_split_fires(monkeypatch):
    """A scatter that lands the quantized page bytes but never updates
    the scale columns produces a pair that LOOKS self-consistent yet
    dequantizes up to the quant range away from the true K/V — only the
    ground-truth recomputation can see it."""
    from burst_attn_tpu.analysis import poolcheck
    from burst_attn_tpu.serving import engine as eng_mod

    real = eng_mod.ragged_model_step

    def split_step(params, toks, q_lens, state, cfg, **kw):
        out = real(params, toks, q_lens, state, cfg, **kw)
        ns = out[1]
        if ns.k_scales is not None:
            ns = ns._replace(
                k_scales=tuple(jnp.ones_like(s) for s in ns.k_scales),
                v_scales=tuple(jnp.ones_like(s) for s in ns.v_scales))
        return (out[0], ns) + tuple(out[2:])

    monkeypatch.setattr(eng_mod, "ragged_model_step", split_step)
    findings = poolcheck._check_quant()
    assert _rules_of(findings) == {"pool-quant-safe"}
    assert any("scatter landed the page without its scale" in f.message
               for f in findings), [f.format() for f in findings]


# ---------------------------------------------------------------------------
# proto-* model-checked protocol rules (ISSUE 15): burstcheck BFS-explores
# every interleaving of the protocol machines (crash injected at every
# step).  The machines below are the SAME module-level functions production
# delegates to (tests/test_protocols.py proves the delegation), so each
# mutation here is a defect both the checker and the serving stack would
# execute — and each must fire exactly one proto-* rule with a minimal
# counterexample trace in the message.


def test_protocheck_rules_registered_and_anchored():
    from burst_attn_tpu.analysis import protocheck

    for name in ("proto-transfer-atomic", "proto-journal-durable",
                 "proto-pool-conserved", "proto-no-deadlock"):
        assert name in RULES and RULES[name].kind == "model"
    # anchors must resolve into the production code that EXECUTES the
    # violated machine, not <trace>
    for model, tail in (("transfer", "kvplane.py"),
                        ("journal", "checkpoint.py"),
                        ("pool", "paged_decode.py")):
        path, line = protocheck._anchor(model)
        assert path.endswith(tail) and line > 0, (model, path)


def test_proto_journal_dropped_fsync_fires(monkeypatch):
    """The fsync barrier silently no-op'd: the engine's step boundary
    delivers tokens that were never durable — a crash un-happens
    delivered output.  proto-journal-durable must produce the minimal
    generate -> step-boundary counterexample."""
    from burst_attn_tpu.analysis import protocheck
    from burst_attn_tpu.protocols import journal as jp

    real = jp.step

    def dropped_fsync(st, ev):
        if ev[0] == "sync":
            return st, ()
        return real(st, ev)

    monkeypatch.setattr(jp, "step", dropped_fsync)
    findings = protocheck.check_all()
    assert _rules_of(findings) == {"proto-journal-durable"}
    msg = findings[0].message
    assert "counterexample" in msg and "DurabilityViolation" in msg
    assert "engine step boundary" in msg
    assert findings[0].file.endswith("checkpoint.py")


def test_proto_journal_pipelined_lagged_delivery_fires():
    """ISSUE 20 delivery lag: the pipelined step boundary journals the
    deferred readback, fsyncs, THEN delivers — one step after the token
    was generated on device.  Reorder deliver before sync on that ONE
    transition (the synchronous boundary stays correct) and the checker
    must find a counterexample that goes THROUGH the pipelined launch:
    the lagged path is proven independently of the synchronous one."""
    from burst_attn_tpu.analysis import modelcheck as mc
    from burst_attn_tpu.protocols import journal as jp

    base = mc.journal_model()

    def transitions(s):
        out = []
        for label, nxt in base.transitions(s):
            if label.startswith("pipelined step boundary"):
                def lagged_deliver_first(s=s):
                    j1, _ = jp.step(s.j, ("append", "tokens", mc._RID, 1))
                    # BUG under test: results leave before the deferred
                    # readback's fsync barrier
                    j2, _ = jp.step(j1, ("deliver", mc._RID, s.gen + 1))
                    j3, _ = jp.step(j2, ("sync",))
                    return mc.JournalModelState(j3, s.gen + 1, 0)
                out.append(mc.guarded(label, lagged_deliver_first))
            else:
                out.append((label, nxt))
        return tuple(out)

    mutated = base._replace(transitions=transitions)
    r = mc.check(mutated, max_depth=24, max_states=50_000)
    assert not r.ok and r.violation is not None
    assert "DurabilityViolation" in r.violation.message
    assert r.violation.trace == (
        "pipelined launch (defer readback)",
        "pipelined step boundary (readback + sync + deliver)"), \
        r.violation.trace


def test_proto_transfer_skipped_preconditions_fires(monkeypatch):
    """commit_preconditions skipped (every control check gone): a
    kv_end that outlives a receiver restart commits a half-shipped
    transfer — pool pages materialize that never crossed the wire."""
    from burst_attn_tpu.analysis import protocheck
    from burst_attn_tpu.protocols import kvtransfer as kvp

    def no_checks(st, rid, slot):
        ent = kvp.staged_entry(st, rid)
        return ent[1] if ent is not None else 2

    monkeypatch.setattr(kvp, "commit_preconditions", no_checks)
    findings = protocheck.check_all()
    assert _rules_of(findings) == {"proto-transfer-atomic"}
    msg = findings[0].message
    assert "counterexample" in msg
    assert "atomicity broken" in msg or "never shipped" in msg
    assert findings[0].file.endswith("kvplane.py")


def test_proto_transfer_eager_staging_leak_fires(monkeypatch):
    """A receiver that acquires pool pages while STAGING (instead of at
    commit) leaks them on any kill/abort mid-transfer — the checker's
    held-vs-owned census catches the very first staged page."""
    from burst_attn_tpu.analysis import protocheck
    from burst_attn_tpu.protocols import kvtransfer as kvp
    from burst_attn_tpu.protocols import pool as pl

    real = kvp.recv_step

    def eager(st, ev):
        if ev[0] == "page":
            npool, _ = pl.step(st.pool, ("acquire", 1))
            st = st._replace(pool=npool)
        return real(st, ev)

    monkeypatch.setattr(kvp, "recv_step", eager)
    findings = protocheck.check_all()
    assert _rules_of(findings) == {"proto-transfer-atomic"}
    msg = findings[0].message
    assert "leak" in msg and "counterexample" in msg


def test_proto_pool_noop_cow_fires(monkeypatch):
    """The CoW privatization no-op'd (returns the same shared page):
    B's append writes into a page the prefix cache still references —
    the machine's own write barrier fires under the interleaving where
    the cache entry is live."""
    from burst_attn_tpu.analysis import protocheck
    from burst_attn_tpu.protocols import pool as pp

    real = pp.step

    def no_cow(st, ev):
        if ev[0] == "cow":
            return st, (("cow", ev[1], ev[1]),)
        return real(st, ev)

    monkeypatch.setattr(pp, "step", no_cow)
    findings = protocheck.check_all()
    assert _rules_of(findings) == {"proto-pool-conserved"}
    msg = findings[0].message
    assert "CowViolation" in msg and "counterexample" in msg
    assert "append B (CoW barrier + write)" in msg
    assert findings[0].file.endswith("paged_decode.py")


def test_proto_credit_window_deadlock_fires(monkeypatch):
    """A per-page credit window against the commit-time-only ack is a
    circular wait: the sender stalls for credits the receiver only
    grants after kv_end, which the sender can never ship.  Bounded
    liveness (proto-no-deadlock) must catch the wedge."""
    from burst_attn_tpu.analysis import protocheck
    from burst_attn_tpu.protocols import kvtransfer as kvp

    monkeypatch.setattr(kvp, "PAGE_CREDIT_WINDOW", 1)
    findings = protocheck.check_all()
    assert _rules_of(findings) == {"proto-no-deadlock"}
    msg = findings[0].message
    assert "deadlock" in msg and "counterexample" in msg


def test_proto_transfer_scale_pair_split_fires(monkeypatch):
    """SCALE_PAIRED mutated off: quantized kv_page frames carry the page
    half only, so scale sidecars stop mirroring the staged page set —
    the quantized transfer model's pair invariant must fire while the
    full-precision model stays clean."""
    from burst_attn_tpu.analysis import protocheck
    from burst_attn_tpu.protocols import kvtransfer as kvp

    monkeypatch.setattr(kvp, "SCALE_PAIRED", False)
    findings = protocheck.check_all()
    assert _rules_of(findings) == {"proto-transfer-atomic"}
    msg = findings[0].message
    assert "staging split" in msg and "counterexample" in msg
    assert findings[0].file.endswith("kvplane.py")


# ---------------------------------------------------------------------------
# ragged-serve-safe mutations: the serving kernel's static contract.
# Each seeds one contract violation into the traced launch and the rule
# must fire (the clean run rides tier-1 via test_clean_run_on_real_package).


def _fake_ragged(body):
    """A stand-in for ragged_paged_attention with the production call
    signature; `body(q_lens)` runs inside the trace."""

    def kernel(q, kp, vp, table, q_lens, kv_lens, k_scales=None,
               v_scales=None, interpret=True):
        body(q_lens)
        return q

    return kernel


def test_servecheck_callback_in_launch_fires(monkeypatch):
    from burst_attn_tpu.analysis import servecheck
    from burst_attn_tpu.ops import ragged_paged

    monkeypatch.setattr(
        ragged_paged, "ragged_paged_attention",
        _fake_ragged(lambda lens: jax.debug.callback(lambda v: None, lens)))
    findings = servecheck.check_all()
    assert "ragged-serve-safe" in _rules_of(findings)
    assert any("host-callback" in f.message for f in findings), [
        f.format() for f in findings]


def test_servecheck_remote_dma_census_fires(monkeypatch):
    from burst_attn_tpu.analysis import ringcheck, servecheck

    monkeypatch.setattr(ringcheck, "_remote_dma_starts",
                        lambda jx: ["dma_start"])
    findings = servecheck.check_all()
    assert "ragged-serve-safe" in _rules_of(findings)
    assert any("remote DMA" in f.message and "census" in f.message
               for f in findings), [f.format() for f in findings]


def test_servecheck_trace_failure_fires(monkeypatch):
    """A host concretization of traced q_lens (`int()` on a tracer)
    breaks jit-safety for the engine — the trace failure IS the
    finding, at every launch width."""
    from burst_attn_tpu.analysis import servecheck
    from burst_attn_tpu.ops import ragged_paged

    monkeypatch.setattr(ragged_paged, "ragged_paged_attention",
                        _fake_ragged(lambda lens: int(lens[0])))
    findings = servecheck.check_all()
    assert len(findings) == 3  # all three engine-width cases fail
    assert all(f.rule == "ragged-serve-safe"
               and "not jit-safe" in f.message for f in findings)


# ---------------------------------------------------------------------------
# output formats: the pinned JSON and SARIF 2.1.0 shapes CI consumes.
# render_sarif's docstring points here — grow the schema additively or
# change these asserts with intent.


def test_json_render_round_trips():
    import json

    from burst_attn_tpu.analysis.core import Finding, render

    findings = [Finding(rule="time-in-jit", message="m", file="f.py",
                        line=3)]
    d = json.loads(render(findings, as_json=True))
    assert set(d) == {"rules_registered", "n_findings", "findings"}
    assert d["rules_registered"] == sorted(RULES)
    assert d["n_findings"] == 1
    assert d["findings"][0] == {"rule": "time-in-jit", "message": "m",
                                "file": "f.py", "line": 3}


def test_sarif_round_trips_pinned_schema():
    import json

    # force full registration so the SARIF rule table is complete
    from burst_attn_tpu.analysis import (astlint, costcheck,  # noqa: F401
                                         numerics, obscheck, poolcheck,
                                         protocheck, ringcheck, servecheck)
    from burst_attn_tpu.analysis.core import Finding, render_sarif

    findings = [
        Finding(rule="silent-except", message="swallowed",
                file="burst_attn_tpu/x.py", line=12),
        Finding(rule="proto-no-deadlock", message="wedged"),  # line=0
    ]
    d = json.loads(render_sarif(findings))
    assert d["version"] == "2.1.0"
    assert "sarif-schema-2.1.0" in d["$schema"]
    assert len(d["runs"]) == 1
    driver = d["runs"][0]["tool"]["driver"]
    assert driver["name"] == "burstlint"
    ids = [r["id"] for r in driver["rules"]]
    assert ids == sorted(RULES)
    for r in driver["rules"]:
        assert r["shortDescription"]["text"] == RULES[r["id"]].doc
        assert r["properties"]["kind"] == RULES[r["id"]].kind
    results = d["runs"][0]["results"]
    assert [x["ruleId"] for x in results] == ["silent-except",
                                              "proto-no-deadlock"]
    for x in results:
        assert x["level"] == "error"
    loc = results[0]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("burst_attn_tpu/x.py")
    assert loc["region"]["startLine"] == 12
    # line 0 (no anchor) clamps to SARIF's 1-based minimum
    loc0 = results[1]["locations"][0]["physicalLocation"]
    assert loc0["region"]["startLine"] == 1


def test_cli_sarif_flag_writes_file(tmp_path):
    import json

    from burst_attn_tpu.analysis.__main__ import main

    out = tmp_path / "nested" / "burstlint.sarif"
    rc = main(["--ast-only", "--sarif", str(out)])
    assert rc == 0
    d = json.loads(out.read_text())
    assert d["version"] == "2.1.0"
    assert d["runs"][0]["results"] == []


# ---------------------------------------------------------------------------
# --changed-only incremental mode: AST rules restricted to the changed
# set, dynamic families skipped when their watchlist is untouched, FULL
# run whenever git can't answer.


def _spy_families(monkeypatch):
    """Stub every dynamic family's check_all with a recorder."""
    from burst_attn_tpu.analysis import (costcheck, numerics, obscheck,
                                         poolcheck, protocheck, ringcheck,
                                         servecheck)

    ran = []
    for name, mod in (("ringcheck", ringcheck), ("numerics", numerics),
                      ("obscheck", obscheck), ("servecheck", servecheck),
                      ("poolcheck", poolcheck), ("protocheck", protocheck),
                      ("costcheck", costcheck)):
        monkeypatch.setattr(mod, "check_all",
                            lambda name=name: (ran.append(name), [])[1])
    return ran


def test_changed_only_runs_touched_families_only(monkeypatch):
    from burst_attn_tpu.analysis import core

    ran = _spy_families(monkeypatch)
    monkeypatch.setattr(
        core, "changed_files",
        lambda root: ["/r/burst_attn_tpu/protocols/pool.py"])
    findings = core.run_analysis(changed_only=True)
    # protocols/ is watched by protocheck alone; the changed path is not
    # a real AST lint target so the AST pass sees zero files
    assert ran == ["protocheck"]
    assert findings == []


def test_changed_only_empty_change_set_skips_everything(monkeypatch):
    from burst_attn_tpu.analysis import core

    ran = _spy_families(monkeypatch)
    monkeypatch.setattr(core, "changed_files", lambda root: [])
    assert core.run_analysis(changed_only=True) == []
    assert ran == []


def test_changed_only_falls_back_to_full_run_without_git(monkeypatch):
    from burst_attn_tpu.analysis import core

    ran = _spy_families(monkeypatch)
    monkeypatch.setattr(core, "changed_files", lambda root: None)
    core.run_analysis(changed_only=True)
    # git unavailable: the incremental mode must degrade to the FULL
    # dynamic sweep, never a silent skip
    assert sorted(ran) == ["costcheck", "numerics", "obscheck",
                           "poolcheck", "protocheck", "ringcheck",
                           "servecheck"]


def test_changed_files_on_this_repo_answers_or_declines():
    import os

    from burst_attn_tpu.analysis import core

    root = os.path.dirname(os.path.abspath(core.__file__))
    got = core.changed_files(root)
    assert got is None or isinstance(got, list)
    if got is not None:
        assert all(os.path.isabs(p) for p in got)


# ---------------------------------------------------------------------------
# cost-* family (burstcost, ISSUE 16): clean on the real tables, and a
# window-blind pair function (cost-model-consistent) and a fwd<bwd table
# inversion (tuning-table-sound) each fire their rule.


def _v5e_row(**overrides):
    from burst_attn_tpu.ops import tuning

    return tuning.generation_row("v5e")._replace(**overrides)


def test_cost_family_clean_on_real_tables():
    from burst_attn_tpu.analysis import costcheck

    findings = costcheck.check_all()
    assert findings == [], "\n".join(f.format() for f in findings)


def test_cost_model_consistent_fires_on_dropped_elision_term():
    """A pair function that ignores the window term (counts the full
    causal triangle) splits from the closed form on the windowed/elided
    case — the devstats counters would integrate the wrong FLOPs."""
    from burst_attn_tpu.analysis import costcheck
    from burst_attn_tpu.ops.masks import _host_round_pairs

    def window_blind(layout, q_part, kv_part, s, causal, window):
        return _host_round_pairs(layout, q_part, kv_part, s, causal, None)

    findings = costcheck.check_cost_consistency(pair_fn=window_blind)
    assert any(f.rule == "cost-model-consistent"
               and "pair algebra split" in f.message for f in findings)


def test_tuning_table_sound_fires_on_fwd_bwd_inversion():
    """A RAW bwd cliff area above its fwd partner: the rule checks the raw
    fields, so a clamp downstream cannot hide the inversion."""
    from burst_attn_tpu.analysis import costcheck

    row = _v5e_row(bwd_cliff_area=4096 * 2048, fwd_cliff_area=2048 * 2048)
    findings = costcheck.check_tuning_sound(table=row)
    assert any(f.rule == "tuning-table-sound"
               and "bwd_cliff_area" in f.message for f in findings)


def test_cost_json_cli_pinned_schema(capsys):
    """--cost-json prints the burstcost-v3 table: the machine-readable
    roofline matrix fleet/sim.py prices with, the ragged serving plans and
    `ragged_hbm`, the per-pool-dtype decode bandwidth pricing (v3 dropped
    the VMEM-plan columns of ring kernels that are gone).  Grow the schema
    additively or change these asserts with intent."""
    import json

    from burst_attn_tpu.analysis.__main__ import main

    assert main(["--cost-json"]) == 0
    d = json.loads(capsys.readouterr().out)
    assert d["schema"] == "burstcost-v3"
    assert set(d) == {"schema", "world", "shape", "hw", "n_rows", "rows",
                      "ragged", "ragged_hbm"}
    assert d["world"] == 8
    assert set(d["shape"]) == {"b", "n", "n_kv", "s", "d"}
    # 5 generations (4 named + default) x 3 topologies x 3 wires x 2 passes
    assert d["n_rows"] == len(d["rows"]) == 90
    row_keys = {"generation", "topology", "wire", "pass", "n_rounds",
                "flops", "hbm_bytes", "ici_bytes", "t_compute_s",
                "t_comm_s"}
    for row in d["rows"]:
        assert set(row) == row_keys
    assert d["ragged"]
    for row in d["ragged"]:
        assert row["fits"] is True, row
    # per-pool-dtype decode HBM pricing — 2 d_heads x 3 pool dtypes,
    # and the 1 B/elem pools must show the analytic bandwidth win
    assert len(d["ragged_hbm"]) == 6
    hbm_keys = {"d_head", "n_kv", "kv_len", "pool_dtype", "kv_elem_bytes",
                "hbm_bytes", "win_vs_fp32"}
    for row in d["ragged_hbm"]:
        assert set(row) == hbm_keys
        assert row["pool_dtype"] in {"fp32", "int8", "fp8"}
        if row["pool_dtype"] == "fp32":
            assert row["win_vs_fp32"] == 1.0
        else:
            assert row["win_vs_fp32"] > 2.0, row
    for spec in d["hw"].values():
        assert set(spec) == {"peak_flops", "hbm_bw", "ici_bw"}


# -- policy-pure (burstlint rule 28, analysis/policycheck.py) ----------------


def _policy_src():
    import os

    import burst_attn_tpu.fleet.policy as pol

    with open(os.path.abspath(pol.__file__), encoding="utf-8") as f:
        return f.read()


def test_policy_pure_rule_registered_at_28_rules():
    from burst_attn_tpu.analysis import (astlint, costcheck,  # noqa: F401
                                         numerics, obscheck, policycheck,
                                         poolcheck, protocheck, ringcheck,
                                         servecheck)

    assert "policy-pure" in RULES
    assert RULES["policy-pure"].kind == "ast"
    assert len(RULES) >= 28


def test_policy_pure_clean_on_real_module():
    from burst_attn_tpu.analysis import policycheck

    assert policycheck.check_all() == []
    # zero suppressions anywhere in the policy module
    assert "burstlint:" not in _policy_src()


def test_policy_pure_smuggled_wall_clock_fires():
    from burst_attn_tpu.analysis import policycheck

    src = _policy_src().replace(
        "best = None\n    best_score = None",
        "best = None\n    import time\n"
        "    _now = time.time()\n    best_score = None", 1)
    assert src != _policy_src()
    findings = policycheck.check_policy_source(src)
    msgs = " | ".join(f.message for f in findings)
    assert "time" in msgs and findings, msgs


def test_policy_pure_module_level_counter_fires():
    from burst_attn_tpu.analysis import policycheck

    src = _policy_src() + (
        "\n_CALLS = 0\n\n\ndef counting_route(state, req=None):\n"
        "    global _CALLS\n    _CALLS += 1\n"
        "    return route_least_loaded(state, req)\n")
    findings = policycheck.check_policy_source(src)
    assert any("global" in f.message for f in findings), findings


def test_policy_pure_module_state_mutation_fires():
    from burst_attn_tpu.analysis import policycheck

    src = _policy_src() + (
        "\n\ndef sneaky(state):\n"
        "    POLICIES.update({})\n"
        "    ROUTE_POLICY_FUNCS[\"x\"] = \"y\"\n    return None\n")
    findings = policycheck.check_policy_source(src)
    assert sum("POLICIES" in f.message
               or "ROUTE_POLICY_FUNCS" in f.message
               for f in findings) >= 2, findings


def test_policy_pure_transport_import_fires():
    from burst_attn_tpu.analysis import policycheck

    for stmt in ("import socket\n",
                 "from burst_attn_tpu.fleet import transport\n",
                 "import numpy as np\n"):
        src = stmt + _policy_src()
        findings = policycheck.check_policy_source(src)
        assert any("import" in f.message for f in findings), stmt


def test_policy_pure_rng_call_fires():
    from burst_attn_tpu.analysis import policycheck

    src = _policy_src().replace(
        "best = None\n    best_score = None",
        "best = None\n    _r = random.random()\n    best_score = None", 1)
    findings = policycheck.check_policy_source(src)
    assert any("RNG" in f.message or "random" in f.message
               for f in findings), findings
