"""The train step measured from inside: the `obs.model.*` / `obs.train.*`
named scopes (metadata only), `obs.begin` / `obs.end`, the per-step
`train.step` record of `make_train_step`, the loader's stall counter and
`fit(on_step=...)`."""

import contextlib
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from burst_attn_tpu import obs
from burst_attn_tpu.data import DataLoader, write_token_file
from burst_attn_tpu.models import train
from burst_attn_tpu.models.runner import RunConfig, fit
from burst_attn_tpu.models.transformer import ModelConfig
from burst_attn_tpu.obs import spans

MODEL_SCOPES = ("obs.model.embed", "obs.model.attn", "obs.model.mlp",
                "obs.model.loss_head", "obs.train.loss",
                "obs.train.optimizer")


def _cfg(**kw):
    kw = {"n_layers": 2, "remat": True, "batch_axis": None,
          "head_axis": None, **kw}
    return ModelConfig(vocab=128, d_model=32, n_heads=2, n_kv_heads=2,
                       d_head=16, d_ff=64, block_q=16, block_kv=16,
                       seq_axes=("sp",), attn_backend="jnp", **kw)


@pytest.fixture(scope="module")
def tiny():
    """(cfg, tcfg, mesh, abstract state, abstract batch) of a 2-layer model
    on one device."""
    cfg, tcfg = _cfg(), train.TrainConfig()
    mesh = train.make_mesh({"sp": 1})
    state = jax.eval_shape(
        lambda key: train.init_train_state(key, cfg, tcfg, mesh),
        jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    batch = {"tokens": tokens, "positions": tokens, "labels": tokens}
    return cfg, tcfg, mesh, state, batch


@pytest.fixture(scope="module")
def data_path(tmp_path_factory):
    path = tmp_path_factory.mktemp("tracing") / "toks.batd"
    write_token_file(path, np.random.default_rng(3).integers(0, 128, 20_000))
    return str(path)


# -- device side: scopes ------------------------------------------------------

@pytest.fixture(scope="module")
def step_text(tiny):
    cfg, tcfg, mesh, state, batch = tiny
    return train.jit_train_step(cfg, tcfg, mesh).lower(state, batch).as_text(
        debug_info=True)


@pytest.mark.parametrize("scope", MODEL_SCOPES)
def test_every_scope_is_in_the_lowered_step(step_text, scope):
    assert scope in step_text


def test_the_lowered_step_tells_forward_recomputed_and_backward_apart(
        step_text):
    # what obs.phase_of reads: JAX's own components around the scopes
    assert "jvp(obs.model.attn)" in step_text
    assert "checkpoint/rematted_computation/obs.model.mlp" in step_text
    assert "transpose(jvp(obs.model.loss_head))" in step_text


def test_the_scopes_add_no_equation_to_the_step(tiny, monkeypatch):
    cfg, tcfg, mesh, state, batch = tiny

    def jaxpr():
        # a fresh trace each time: the jitted step caches its own
        fn = train.jit_train_step(cfg, tcfg, mesh)
        return str(jax.make_jaxpr(fn)(state, batch))

    scoped = jaxpr()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert jaxpr() == scoped  # the same equations in the same order
    assert scoped.count("dot_general") > 10  # and it is the whole step


# -- obs.begin / obs.end ------------------------------------------------------

def test_begin_end_nest_spans_entered_meanwhile():
    obs.reset_spans()
    outer = obs.begin("t.outer", k=1)
    with obs.span("t.child"):
        pass
    with obs.span("t.child"):
        pass
    with obs.span("t.other") as other:
        assert other.parent_id == outer.span_id and other.depth == 1
    done = obs.end(outer)
    assert done.name == "t.outer" and done.attrs == {"k": 1}
    assert set(outer.child_s) == {"t.child", "t.other"}
    by_name = {}
    for s in obs.completed_spans():
        by_name.setdefault(s.name, []).append(s)
    assert len(by_name["t.child"]) == 2
    assert all(c.parent_id == done.span_id for c in by_name["t.child"])
    assert outer.child_s["t.child"] == pytest.approx(
        sum(c.duration_s for c in by_name["t.child"]))
    assert done.duration_s >= sum(outer.child_s.values())
    assert obs.current_span() is None


def test_a_span_opened_across_calls_is_a_root_and_ends_under_a_later_one():
    obs.reset_spans()
    with obs.span("t.caller") as caller:
        kept = obs.begin("t.kept")  # stays open past its opener's block
        assert (kept.parent_id, kept.depth) == (None, 0)
        assert caller.child_s == {}  # and is no child of it
    assert obs.current_span() is kept
    with obs.span("t.later") as later:
        assert (later.parent_id, later.depth) == (kept.span_id, 1)
        obs.end(kept)  # ended from under a span entered later
        assert obs.current_span() is later
    assert obs.current_span() is None
    assert [s.name for s in obs.completed_spans()] == [
        "t.caller", "t.kept", "t.later"]


def test_an_ended_span_lets_go_of_its_parent_and_keeps_the_id():
    obs.reset_spans()
    with obs.span("t.parent") as parent:
        with obs.span("t.child") as child:
            assert child.parent is parent
        assert child.parent is None and child.parent_id == parent.span_id
    done = {s.name: s for s in obs.completed_spans()}
    assert done["t.child"].parent_id == done["t.parent"].span_id
    assert (done["t.parent"].depth, done["t.child"].depth) == (0, 1)


def test_a_span_whose_handle_is_dropped_adopts_nothing_after():
    obs.reset_spans()
    kept = obs.begin("t.kept")
    gone = obs.begin("t.gone")
    assert obs.current_span() is gone
    del gone  # its holder went away without end(): it is no longer open
    assert obs.current_span() is kept
    with obs.span("t.later") as later:
        assert later.parent_id == kept.span_id
    obs.end(kept)
    assert obs.current_span() is None
    assert [s.name for s in obs.completed_spans()] == ["t.later", "t.kept"]


def test_end_can_leave_the_spans_histogram_alone():
    kept = obs.histogram("span.t.unobserved")
    n0 = kept.get()["count"]
    obs.end(obs.begin("t.unobserved"), observe=False)
    assert kept.get()["count"] == n0
    obs.end(obs.begin("t.unobserved"))
    assert kept.get()["count"] == n0 + 1


def test_begin_under_a_trace_is_the_noop_handle():
    obs.reset_spans()

    @jax.jit
    def f(x):
        live = obs.begin("t.traced")
        live.set("k", 1)
        assert obs.end(live) is None
        return x + 1

    f(jnp.zeros(2))
    assert obs.completed_spans() == [] and obs.current_span() is None


def test_spans_of_two_threads_keep_their_own_stacks():
    obs.reset_spans()
    main = obs.begin("t.main")
    seen = {}

    def worker():
        with obs.span("t.worker") as sp:
            seen["parent"], seen["depth"] = sp.parent_id, sp.depth

    thread = threading.Thread(target=worker)
    thread.start()
    thread.join(timeout=30)
    assert not thread.is_alive()
    obs.end(main)
    assert seen == {"parent": None, "depth": 0}


# -- host side: the per-step record ------------------------------------------

def _host_batches(n, rng, batch=2, seq=32):
    return [(rng.integers(0, 128, (batch, seq)).astype(np.int32),
             rng.integers(0, 128, (batch, seq)).astype(np.int32))
            for _ in range(n)]


def _counts(*names):
    return [obs.histogram(n).get()["count"] for n in names]


HISTOGRAMS = ("train.step_interval_s", "span.train.step",
              "span.train.dispatch")


@pytest.fixture(scope="module")
def five_steps():
    """What a tiny trainer leaves behind, compiled once: five plain steps
    and a `close()`, then three steps each inside a span of the caller's
    that nobody closes the step function under."""
    cfg, tcfg = _cfg(n_layers=1, remat=False), train.TrainConfig()
    mesh = train.make_mesh({"sp": 1})
    state = train.init_train_state(jax.random.PRNGKey(0), cfg, tcfg, mesh)
    src = _host_batches(12, np.random.default_rng(0))
    obs.reset_spans()
    before = _counts(*HISTOGRAMS)
    batches = train.prefetch_batches(iter(src), cfg, mesh)
    step = train.make_train_step(cfg, tcfg, mesh)
    for _ in range(5):
        state, _ = step(state, next(batches))
        jax.block_until_ready(state)
    closed = step.close()
    out = {"plain": obs.completed_spans(), "closed": closed,
           "closed_again": step.close(), "top_after_close": obs.current_span(),
           "observed": [b - a for a, b in zip(before, _counts(*HISTOGRAMS))]}
    obs.reset_spans()
    for _ in range(3):
        with obs.span("t.iter"):
            state, _ = step(state, next(batches))
            jax.block_until_ready(state)
    out["still_open"] = obs.current_span()
    step.close()
    out["wrapped"] = obs.completed_spans()
    return out


def test_train_step_records_of_five_steps(five_steps):
    done = five_steps["plain"]
    records = [s for s in done if s.name == "train.step"]
    assert records[-1] is five_steps["closed"]  # close() hands over the last
    assert five_steps["closed_again"] is None  # and nothing is open then
    assert five_steps["top_after_close"] is None
    assert [r.attrs["seq"] for r in records] == [0, 1, 2, 3, 4]
    assert [(r.parent_id, r.depth) for r in records] == [(None, 0)] * 5
    for record in records:
        kids = [s for s in done if s.parent_id == record.span_id]
        assert {k.name for k in kids} <= {
            "train.dispatch", "train.loader_wait", "train.h2d"}
        assert [k.name for k in kids].count("train.dispatch") == 1
        a = record.attrs
        for attr, child in (("dispatch_s", "train.dispatch"),
                            ("loader_wait_s", "train.loader_wait"),
                            ("h2d_s", "train.h2d")):
            assert a[attr] == pytest.approx(
                sum(k.duration_s for k in kids if k.name == child))
        assert (a["loader_wait_s"] + a["h2d_s"] + a["dispatch_s"]
                <= record.duration_s)
        assert a["gc2"] >= 0 and a["wall_ns"] > 1.6e18
    # the first dispatch compiles; none after it does
    assert records[0].attrs["compiles"] >= 1
    assert [r.attrs["compiles"] for r in records[1:]] == [0] * 4
    # a step's span ends where the next begins, and the walls agree
    for before, after in zip(records, records[1:]):
        gap = after.start_s - (before.start_s + before.duration_s)
        assert 0 <= gap < 5e-3
        wall = (after.attrs["wall_ns"] - before.attrs["wall_ns"]) * 1e-9
        assert wall == pytest.approx(after.start_s - before.start_s,
                                     abs=5e-3)
    # the spans carry nothing that no one reads
    assert all(s.attrs == {} for s in done if s.name != "train.step")


def test_the_step_interval_has_one_histogram_and_skips_the_compile(
        five_steps):
    # five dispatches: four intervals, of which the first holds the compile
    interval, span_step, span_dispatch = five_steps["observed"]
    assert (interval, span_step, span_dispatch) == (3, 0, 5)


def test_the_scheduling_counter_is_there_or_absent_never_zero_filled(
        five_steps):
    records = [s for s in five_steps["plain"] if s.name == "train.step"]
    counted = train._nivcsw() is not None
    assert all(("nivcsw" in r.attrs) == counted for r in records)
    assert all(r.attrs.get("nivcsw", 0) >= 0 for r in records)


def test_steps_dispatched_inside_a_callers_span_do_not_chain(five_steps):
    # each train.step begins inside a `t.iter` that began under the last
    # train.step: as children of what was open they would nest run-deep
    done, top = five_steps["wrapped"], five_steps["still_open"]
    assert top.name == "train.step" and top.parent is None
    steps = [s for s in done if s.name == "train.step"]
    iters = [s for s in done if s.name == "t.iter"]
    assert len(steps) == len(iters) == 3
    assert [(s.parent_id, s.depth) for s in steps] == [(None, 0)] * 3
    # the caller's span hangs under the step open when it began (the first
    # follows the close(): under none), one level down and no further
    assert [i.parent_id for i in iters] == [
        None, steps[0].span_id, steps[1].span_id]
    assert [i.depth for i in iters] == [0, 1, 1]
    assert max(s.depth for s in done) <= 2


def test_the_one_shot_train_step_leaves_no_span_open():
    cfg, tcfg = _cfg(n_layers=1, remat=False), train.TrainConfig()
    mesh = train.make_mesh({"sp": 1})
    state = train.init_train_state(jax.random.PRNGKey(0), cfg, tcfg, mesh)
    batch = train.make_batch(jax.random.PRNGKey(1), cfg, mesh, 2, 32)
    obs.reset_spans()
    train.train_step(state, batch, cfg, tcfg, mesh)
    assert obs.current_span() is None
    assert [s.name for s in obs.completed_spans()] == [
        "train.dispatch", "train.step"]


def test_prefetch_yields_the_same_batches_in_the_same_order():
    cfg = _cfg(n_layers=1, remat=False, layout="contig")
    mesh = train.make_mesh({"sp": 2})
    src = _host_batches(6, np.random.default_rng(1))
    want = [train.batch_from_host(x, y, cfg, mesh) for x, y in src]
    for depth in (1, 2, 8):  # 8: the source is shorter than the queue
        got = list(train.prefetch_batches(iter(src), cfg, mesh, depth=depth))
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert set(g) == set(w)
            for key in w:
                np.testing.assert_array_equal(np.asarray(g[key]),
                                              np.asarray(w[key]))


def test_the_loader_counts_its_stalls(data_path, monkeypatch):
    from burst_attn_tpu.data import loader

    stalls = obs.counter("data.loader_stalls")
    with DataLoader(data_path, 2, 32, num_threads=1) as dl:
        # every wait is a stall once the line is drawn at nothing, and none
        # is once it is drawn out of reach
        monkeypatch.setattr(loader, "STALL_S", -1.0)
        before = stalls.get()
        dl.next()
        assert stalls.get() - before == 1
        monkeypatch.setattr(loader, "STALL_S", 3600.0)
        dl.next()
        assert stalls.get() - before == 1


# -- fit on the same pieces ---------------------------------------------------

def test_fit_hands_on_step_one_record_a_step_and_stops_on_false(data_path):
    cfg = _cfg(n_layers=1, remat=False)
    mesh = train.make_mesh({"sp": 1})
    run = RunConfig(data_path=data_path, steps=6, batch=2, seq_len=32,
                    log_every=2)
    seen = []

    def on_step(record):
        seen.append(record)
        return len(seen) < 4  # False after the fourth step

    obs.reset_spans()
    _, history = fit(cfg, train.TrainConfig(lr=1e-3), run, mesh,
                     on_step=on_step)
    assert [r["step"] for r in seen] == [1, 2, 3, 4]
    assert [r["attrs"]["seq"] for r in seen] == [0, 1, 2, 3]
    assert [h["step"] for h in history] == [2, 4]
    for record in seen:
        assert record["name"] == "train.step"
        assert 0 < record["blocked_s"] <= record["duration_s"]
    # history's step time is the record's blocked time
    assert history[-1]["step_s"] == seen[3]["blocked_s"]
    # the log line is a child of the step it follows; so is the next batch
    done = obs.completed_spans()
    logs = [s for s in done if s.name == "train.log"]
    steps = {s.span_id: s for s in done if s.name == "train.step"}
    assert [steps[s.parent_id].attrs["seq"] for s in logs] == [1, 3]
    waits = [s for s in done if s.name == "train.loader_wait"]
    assert all(s.parent_id in steps for s in waits[3:])  # past the prefill
    assert obs.current_span() is None  # fit closed what it opened


def test_fit_without_a_hook_runs_every_step_and_closes_its_last_span(
        data_path):
    cfg = _cfg(n_layers=1, remat=False)
    mesh = train.make_mesh({"sp": 1})
    run = RunConfig(data_path=data_path, steps=3, batch=2, seq_len=32,
                    log_every=1)
    obs.reset_spans()
    _, history = fit(cfg, train.TrainConfig(lr=1e-3), run, mesh)
    assert [h["step"] for h in history] == [1, 2, 3]
    records = [s for s in obs.completed_spans() if s.name == "train.step"]
    assert [r.attrs["seq"] for r in records] == [0, 1, 2]
    assert obs.current_span() is None


# -- from an op_name back to phase and module ---------------------------------

# op_names as the v5e's compiler printed them for the trainer cells' step
# (PR 25, chip run); the last rows are what carries neither transform nor scope
RECORDED_OP_NAMES = [
    ("jit(step)/jvp(obs.model.embed)/gather", ("fwd", "embed")),
    ("jit(step)/jvp(obs.model.attn)/bsd,dnh->bnsh/dot_general",
     ("fwd", "attn")),
    ("jit(step)/jvp(obs.model.attn)/obs.ring.round0_self/burst_flash_fwd/"
     "pallas_call", ("fwd", "attn")),
    ("jit(step)/jvp(obs.model.mlp)/jit(silu)", ("fwd", "mlp")),
    ("jit(step)/jvp(obs.model.loss_head)/bsd,vd->bsv/dot_general",
     ("fwd", "loss_head")),
    ("jit(step)/jvp(obs.train.loss)/jit(log_softmax)/reduce_max",
     ("fwd", "loss_head")),
    ("jit(step)/jvp()/div", ("fwd", "other")),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "obs.model.attn/obs.ring.round0_self/burst_flash_fwd/pallas_call",
     ("remat", "attn")),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "obs.model.mlp/bsd,df->bsf/dot_general", ("remat", "mlp")),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/obs.model.attn/"
     "bsd,dnh->bnsh/dot_general", ("bwd", "attn")),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/obs.model.mlp/bsf,fd->bsd/"
     "dot_general", ("bwd", "mlp")),
    ("jit(step)/transpose(jvp(obs.model.loss_head))/bsd,vd->bsv/dot_general",
     ("bwd", "loss_head")),
    ("jit(step)/transpose(jvp(obs.train.loss))/jit(take_along_axis)/"
     "scatter-add", ("bwd", "loss_head")),
    ("jit(step)/transpose(jvp(obs.model.embed))/scatter-add",
     ("bwd", "embed")),
    ("jit(step)/transpose(jvp(jvp()))/remat2", ("bwd", "other")),
    ("jit(step)/obs.train.optimizer/jit(_where)/select_n",
     ("optimizer", "other")),
    ("jit(f)/obs.model.mlp/mul", ("fwd", "mlp")),  # a forward with no grad
    ("state[0][\\'layers\\'][3][\\'w_up\\']", ("other", "other")),
    ("reduce_sum", ("other", "other")),
    ("", ("other", "other")),
]


@pytest.mark.parametrize("op_name, want", RECORDED_OP_NAMES)
def test_phase_of_reads_phase_and_module_off_an_op_name(op_name, want):
    got = obs.phase_of(op_name)
    assert got == want
    assert got[0] in spans.PHASES and got[1] in spans.MODULES


# lines of the same executable's text, operand types and backend_config cut
HLO_EXCERPT = r"""
HloModule jit_step, is_scheduled=true

%fused_computation.712 (param_0: bf16[8,1024,4096]) -> bf16[8,8,1024,128] {
  %param_0 = bf16[8,1024,4096]{2,1,0} parameter(0)
  ROOT %convolution.5 = bf16[8,8,1024,128]{3,2,1,0} convolution(%param_0), metadata={op_name="jit(step)/jvp(obs.model.attn)/bsd,dnh->bnsh/dot_general" stack_frame_id=44}
}

%fused_computation.9 (param_0.1: f32[8,1024]) -> bf16[8,1024] {
  %param_0.1 = f32[8,1024]{1,0} parameter(0)
  ROOT %convert.1 = bf16[8,1024]{1,0} convert(%param_0.1), metadata={op_name="jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/obs.model.attn/convert_element_type"}
}

ENTRY %main.1 (state: bf16[4096,8,128]) -> bf16[8,8,1024,128] {
  %state = bf16[4096,8,128]{2,1,0} parameter(0), metadata={op_name="state[0][\'layers\'][0][\'wk\']"}
  %slice-start.1 = ((bf16[4096,8,128]), bf16[1024,8,128], s32[]) slice-start(%state), slice={[0:1024], [0:8], [0:128]}
  %slice-done.1 = bf16[1024,8,128]{2,1,0} slice-done(%slice-start.1)
  %custom-call.2 = bf16[4096,8,128]{2,1,0} custom-call(%slice-done.1), custom_call_target="ConcatBitcast", backend_config={"flag_configs":[]}
  %fusion.539 = bf16[8,8,1024,128]{3,2,1,0} fusion(%custom-call.2), kind=kOutput, calls=%fused_computation.712, metadata={op_name="jit(step)/jvp(obs.model.attn)/bsd,dnh->bnsh/dot_general" stack_frame_id=44}, backend_config={"flag_configs":[]}
  %subtract_convert_fusion.2 = bf16[8,1024]{1,0} fusion(%fusion.539), kind=kLoop, calls=%fused_computation.9
  %add.7 = f32[] add(%fusion.539, %fusion.539), metadata={op_name="jit(step)/obs.train.optimizer/add"}
  %copy-start.3 = (f32[], f32[], u32[]) copy-start(%add.7)
  %copy-done.3 = f32[] copy-done(%copy-start.3)
  ROOT %tuple.1 = (f32[]) tuple(%copy-done.3)
}
"""


def test_scope_map_reads_metadata_and_places_what_the_compiler_added():
    scopes = obs.scope_map(HLO_EXCERPT)
    attn_proj = "jit(step)/jvp(obs.model.attn)/bsd,dnh->bnsh/dot_general"
    # its own metadata, escaped quotes and all
    assert scopes["%fusion.539"] == attn_proj
    assert scopes["%add.7"] == "jit(step)/obs.train.optimizer/add"
    assert scopes["%state"] == "state[0][\\'layers\\'][0][\\'wk\\']"
    # a fusion without metadata: the ROOT of the computation it calls
    assert obs.phase_of(scopes["%subtract_convert_fusion.2"]) == (
        "remat", "attn")
    # a prefetch the compiler put in: the op that consumes it, through the
    # compiler's own bitcast
    assert scopes["%slice-start.1"] == attn_proj
    assert scopes["%slice-done.1"] == attn_proj
    assert scopes["%custom-call.2"] == attn_proj
    # nothing consumes the result copy: the op that made what it copies
    assert scopes["%copy-done.3"] == "jit(step)/obs.train.optimizer/add"
    assert scopes["%tuple.1"] == "jit(step)/obs.train.optimizer/add"
    # instructions inside a fused computation are there too
    assert scopes["%convolution.5"] == attn_proj
    assert obs.scope_map("") == {}
