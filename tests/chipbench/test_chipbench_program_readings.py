"""The readers of the program's own instrumentation
(`chipbench/program_readings.py` and the `layer_metrics` built on it): each
on a synthetic reading, their None paths, and the phase partition on a step
recorded from the chip with its executable's text."""

import gzip
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from burst_attn_tpu import obs  # noqa: E402
from chipbench import program_readings as pr, run, trace as t  # noqa: E402

HERE = Path(__file__).parent
CELL = run.load_cell("train_mistral_8x1k")
NEW_DEVICE = {"fwd_ms_per_step", "remat_ms_per_step", "bwd_ms_per_step",
              "optimizer_ms_per_step", "loss_head_ms_per_step",
              "mlp_ms_per_step", "attn_ms_per_step"}
NEW_HOST = {"loader_wait_ms_per_step", "h2d_ms_per_step",
            "dispatch_ms_per_step"}


def read(metric, reading):
    return run.read_layer_metric(CELL, metric, reading)


# -- host side ----------------------------------------------------------------

def host_reading(monkeypatch, *, warm=3, n=6, k=5, stall=None, h2d=True,
                 drop_newest=0, seq_gap=None):
    """A run's `reading["steps"]` and the ring the program would have left:
    `warm + n + k` dispatches 100 ms apart (`stall`: (window step, excess
    seconds))."""
    total = warm + n + k
    step_s = [0.098] * total
    if stall:
        step_s[warm + stall[0]] += stall[1]
    dispatch = [0.0]
    for s in step_s:
        dispatch.append(dispatch[-1] + s + 0.002)  # 2 ms between steps
    done = [d + s for d, s in zip(dispatch, step_s)]
    steps = []
    for i in range(warm, warm + n):
        steps.append({"wait_s": 0.0012, "dispatch_s": 0.0007,
                      "step_s": step_s[i], "interval_s": done[i] - done[i - 1]})
    records = []
    for i in range(total - 1):  # the last dispatch's span never closes
        attrs = {"seq": i, "wall_ns": 0, "loader_wait_s": 0.0002,
                 "dispatch_s": 0.0007, "compiles": 0, "gc2": 0, "nivcsw": 0}
        if h2d:
            attrs["h2d_s"] = 0.0010
        if seq_gap is not None and i >= warm + seq_gap:
            attrs["seq"] += 1
        records.append(obs.Span(
            name="train.step", span_id=i + 1, parent_id=None, depth=0,
            thread="MainThread", start_s=dispatch[i],
            duration_s=dispatch[i + 1] - dispatch[i], attrs=attrs))
    other = obs.Span(name="train.dispatch", span_id=999, parent_id=1,
                     depth=1, thread="MainThread", start_s=0.0,
                     duration_s=0.0007)
    ring = ([other] + records)[:len(records) + 1 - drop_newest]
    monkeypatch.setattr(pr.obs, "completed_spans", lambda: ring)
    return {"cell": CELL, "steps": steps, "setup": {},
            "trace": {"steps": k, "devices": {}} if k else None}


def test_window_records_are_the_windows_steps_but_the_last(monkeypatch):
    reading = host_reading(monkeypatch, warm=3, n=6, k=5)
    records = pr.window_records(reading)
    assert [r.attrs["seq"] for r in records] == [3, 4, 5, 6, 7]
    # untraced (k = 0): the window's last step has no closed span at all
    reading = host_reading(monkeypatch, warm=2, n=4, k=0)
    assert [r.attrs["seq"] for r in pr.window_records(reading)] == [2, 3, 4]


@pytest.mark.parametrize("metric, want", [
    ("loader_wait_ms_per_step", 0.2), ("h2d_ms_per_step", 1.0),
    ("dispatch_ms_per_step", 0.7)])
def test_host_readers_average_the_records_attrs(monkeypatch, metric, want):
    assert read(metric, host_reading(monkeypatch)) == pytest.approx(want)
    # a stall is in the record's self time: it moves none of these means
    stalled = host_reading(monkeypatch, stall=(2, 0.060))
    assert read(metric, stalled) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(NEW_HOST))
@pytest.mark.parametrize("fault", [
    {"warm": 0, "n": 6, "k": 5, "drop_newest": 11},  # no record at all
    {"warm": 1, "drop_newest": 3},  # fewer than window + traced steps made
    {"seq_gap": 2},  # a record is missing inside the window
    {"stall": (2, 0.060), "k": 4},  # miscounted by one (five steps were
    # traced, see below): the stall sits a step off
    {"n": 1},  # one step: its record runs across the profiler's start
])
def test_host_readers_give_nothing_where_the_records_are_not_the_windows(
        monkeypatch, metric, fault):
    reading = host_reading(monkeypatch, **fault)
    if fault.get("k") == 4:  # the benchmark says five steps were traced
        reading["trace"]["steps"] = 5
    assert read(metric, reading) is None


def test_records_without_an_attr_read_nothing_not_zero(monkeypatch):
    reading = host_reading(monkeypatch, h2d=False)
    assert read("h2d_ms_per_step", reading) is None
    assert read("dispatch_ms_per_step", reading) == pytest.approx(0.7)


def test_host_readers_read_the_ring_a_real_trainer_leaves(monkeypatch):
    """No synthetic spans: five blocked steps of a tiny trainer timed as
    the harness times them, then the readers on the program's own ring."""
    import time

    import jax
    import numpy as np

    from burst_attn_tpu.models import train
    from burst_attn_tpu.models.transformer import ModelConfig

    cfg = ModelConfig(vocab=128, d_model=32, n_layers=1, n_heads=2,
                      n_kv_heads=2, d_head=16, d_ff=64, block_q=16,
                      block_kv=16, seq_axes=("sp",), attn_backend="jnp",
                      remat=False, batch_axis=None, head_axis=None)
    tcfg, mesh = train.TrainConfig(), train.make_mesh({"sp": 1})
    state = train.init_train_state(jax.random.PRNGKey(0), cfg, tcfg, mesh)
    rng = np.random.default_rng(0)
    src = ((rng.integers(0, 128, (2, 32)).astype(np.int32),) * 2
           for _ in range(64))
    batches = train.prefetch_batches(src, cfg, mesh)
    step = train.make_train_step(cfg, tcfg, mesh)
    obs.reset_spans()
    steps, prev = [], None
    for i in range(9):  # 2 warm-up, 5 in the window, 2 "traced"
        t0 = time.perf_counter()
        batch = next(batches)
        t1 = time.perf_counter()
        state, _ = step(state, batch)
        t2 = time.perf_counter()
        jax.block_until_ready(state)
        done = time.perf_counter()
        if 2 <= i < 7:
            steps.append({"wait_s": t1 - t0, "dispatch_s": t2 - t1,
                          "step_s": done - t1, "interval_s": done - prev})
        prev = done
    reading = {"cell": CELL, "steps": steps, "trace": {"steps": 2}}
    records = pr.window_records(reading)
    assert [r.attrs["seq"] for r in records] == [2, 3, 4, 5]
    for metric, outside in (("dispatch_ms_per_step", "dispatch_s"),
                            ("h2d_ms_per_step", "wait_s")):
        inside = read(metric, reading)
        mean = 1e3 * sum(s[outside] for s in steps[:4]) / 4
        assert 0 < inside <= mean + 0.05  # the span sits inside the outer


# -- device side --------------------------------------------------------------

SCOPES = {
    "%fusion.1": "jit(step)/jvp(obs.model.mlp)/bsd,df->bsf/dot_general",
    "%burst_flash_fwd.2": "jit(step)/jvp(obs.model.attn)/"
                          "obs.ring.round0_self/burst_flash_fwd/pallas_call",
    "%fusion.3": "jit(step)/transpose(jvp(jvp()))/checkpoint/"
                 "rematted_computation/obs.model.mlp/mul",
    "%fusion.4": "jit(step)/transpose(jvp(obs.model.loss_head))/"
                 "bsd,vd->bsv/dot_general",
    "%fusion.5": "jit(step)/obs.train.optimizer/add",
    "%fusion.6": "jit(step)/jvp(obs.train.loss)/jit(log_softmax)/exp",
    "%copy.7": "",
}


# the instructions as the executable's text prints them (after the name)
# and their operands' types, which only an event's name carries
PRINTED = {
    "%fusion.1": "bf16[8]{0} fusion({}%p), kind=kOutput, calls=%fc.1",
    "%burst_flash_fwd.2": "(f32[8]{0}) custom-call({}%p)",
    "%fusion.3": "bf16[8]{0} fusion({}%p, {}%fusion.1), kind=kLoop, "
                 "calls=%fc.3",
    "%fusion.4": "f32[8]{0} fusion({}%p), kind=kOutput, calls=%fc.4",
    "%fusion.5": "f32[8]{0} fusion({}%p), kind=kLoop, calls=%fc.5",
    "%fusion.6": "f32[8]{0} fusion({}%p), kind=kLoop, calls=%fc.6",
    "%copy.7": "f32[8]{0} copy({}%p)",
}


def hlo_text(scopes=SCOPES, printed=PRINTED):
    lines = ["HloModule jit_step", "", "ENTRY %main (p: f32[8]) -> f32[8] {"]
    for name, op_name in scopes.items():
        meta = f', metadata={{op_name="{op_name}"}}' if op_name else ""
        text = printed.get(name, "f32[] add({}%p)").replace("{}", "")
        lines.append(f"  {name} = {text}{meta}")
    return "\n".join(lines + ["}"])


def device_reading(monkeypatch, *, unresolved_ms=1.0, text=None):
    ms = 1_000_000
    durations = [40, 20, 30, 50, 10, 6, unresolved_ms]
    names = [(f"{name} = {text_.replace('{}', 'f32[8]{0} ')}", dur)
             for (name, text_), dur in zip(PRINTED.items(), durations)]
    segments, cursor = [], 0
    for _ in range(2):  # two traced steps, the same ops in each
        for name, dur in names:
            segments.append((name, cursor, cursor + int(dur * ms)))
            cursor += int(dur * ms) + 1000
    trace = {"devices": {"/device:TPU:0": segments}, "async": {},
             "spans": [], "window": (0, cursor), "steps": 2}
    monkeypatch.setattr(pr, "step_text",
                        lambda cell, devices: text or hlo_text())
    return {"cell": CELL, "steps": [], "trace": trace}


@pytest.mark.parametrize("metric, want", [
    ("fwd_ms_per_step", 40 + 20 + 6), ("remat_ms_per_step", 30),
    ("bwd_ms_per_step", 50), ("optimizer_ms_per_step", 10),
    ("loss_head_ms_per_step", 50 + 6), ("mlp_ms_per_step", 40 + 30),
    ("attn_ms_per_step", 20)])
def test_device_readers_split_the_step_by_phase_and_module(
        monkeypatch, metric, want):
    assert read(metric, device_reading(monkeypatch)) == pytest.approx(want)


def test_the_phases_partition_the_busy_time(monkeypatch):
    reading = device_reading(monkeypatch)
    phases = sum(read(f"{p}_ms_per_step", reading)
                 for p in ("fwd", "remat", "bwd", "optimizer"))
    busy = read("flash_ms_per_step", reading) + read("xla_ms_per_step",
                                                     reading)
    assert busy == pytest.approx(157.0)
    assert phases == pytest.approx(busy - 1.0)  # all but the unresolved copy
    assert sum(pr.device_ms(reading).values()) == pytest.approx(busy)


def test_the_step_is_compiled_once_for_all_the_readers(monkeypatch):
    reading = device_reading(monkeypatch)
    calls = []
    monkeypatch.setattr(pr, "step_text",
                        lambda cell, devices: calls.append(1) or hlo_text())
    for metric in sorted(NEW_DEVICE):
        read(metric, reading)
    assert calls == [1]


@pytest.mark.parametrize("metric", sorted(NEW_DEVICE))
@pytest.mark.parametrize("why", ["untraced", "no_device_plane",
                                 "under_95_percent", "no_scopes_in_program",
                                 "names_do_not_join", "another_shape",
                                 "another_operand", "another_fusion_kind"])
def test_device_readers_give_nothing_not_zero(monkeypatch, metric, why):
    reading = device_reading(monkeypatch)
    if why == "untraced":
        reading["trace"] = None
    elif why == "no_device_plane":  # the CPU rehearsal
        reading["trace"]["devices"] = {}
    elif why == "under_95_percent":  # 8 of 144 non-flash ms have no phase
        reading = device_reading(monkeypatch, unresolved_ms=8.0)
    elif why == "no_scopes_in_program":  # the parent of this PR
        monkeypatch.setattr(pr, "scope_map", None)
    elif why == "names_do_not_join":  # another program's text
        reading = device_reading(
            monkeypatch, text=hlo_text({"%fusion.900": "x"}))
    else:  # every name joins, and one is not the instruction that ran
        other = {"another_shape": ("f32[8]{0} fusion", "f32[16]{0} fusion"),
                 "another_operand": ("%fusion.1)", "%fusion.4)"),
                 "another_fusion_kind": ("kind=kLoop, calls=%fc.5",
                                         "kind=kInput, calls=%fc.5")}[why]
        assert other[0] in hlo_text()
        reading = device_reading(monkeypatch,
                                 text=hlo_text().replace(*other))
    assert read(metric, reading) is None


# -- a step recorded on the chip ----------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    """One traced step of train_mistral_8x1k with its events' full names,
    and the text of the executable that ran it (PR 25, TPU v5 lite)."""
    with gzip.open(HERE / "trace_events_sample_8x1k.json.gz", "rt") as f:
        raw = json.load(f)
    with gzip.open(HERE / "step_hlo_sample_8x1k.txt.gz", "rt") as f:
        text = f.read()
    return (t.reduce_trace(raw, 1), obs.scope_map(text),
            obs.instruction_texts(text))


def test_recorded_step_every_event_joins_the_executables_text(recorded):
    trace, scopes, texts = recorded
    names = {name for segments in trace["devices"].values()
             for name, _, _ in segments}
    heads = {pr._EVENT_HEAD.match(name).group(0) for name in names}
    assert len(heads) > 1000 and heads <= set(scopes) and heads <= set(texts)
    # and each is the instruction the text has under its name
    assert all(pr.same_instruction(
        name, texts[pr._EVENT_HEAD.match(name).group(0)]) for name in names)


def test_recorded_step_joined_to_a_text_numbered_otherwise_reads_nothing(
        recorded):
    """Another compile of almost the same program: every name still joins,
    a fusion's number is another instruction's."""
    trace, scopes, texts = recorded
    fusions = sorted(n for n in texts if n.startswith("%fusion."))
    shifted = dict(texts)
    shifted.update(zip(fusions, fusions[1:] + fusions[:1]))
    shifted = {n: texts[m] if m in texts else m for n, m in shifted.items()}
    assert pr.split_by_scope(trace, scopes, texts) is not None
    assert pr.split_by_scope(trace, scopes, shifted) is None


def test_recorded_step_phases_partition_flash_plus_xla(recorded):
    trace, scopes, texts = recorded
    split = pr.split_by_scope(trace, scopes, texts)
    reading = {"trace": trace}
    flash = run.read_layer_metric(CELL, "flash_ms_per_step", reading)
    xla = run.read_layer_metric(CELL, "xla_ms_per_step", reading)
    assert sum(split.values()) == pytest.approx(flash + xla, rel=1e-9)
    phases = {p: sum(ms for (q, _), ms in split.items() if q == p)
              for p in ("fwd", "remat", "bwd", "optimizer", "other")}
    assert phases["other"] < 0.01 * (flash + xla)
    assert sum(phases.values()) - phases["other"] == pytest.approx(
        flash + xla, rel=0.01)
    # what the chip run's result line said of the same run, to a step's
    # variation (PERF.md, PR 25)
    note = json.loads(gzip.open(
        HERE / "trace_events_sample_8x1k.json.gz", "rt").read())["reported"]
    for phase in ("fwd", "remat", "bwd", "optimizer"):
        assert phases[phase] == pytest.approx(
            note[f"{phase}_ms_per_step"], rel=0.02)
    # every flash kernel has a phase: forward, recomputed forward, backward
    flash_by_phase = {}
    for segments in trace["devices"].values():
        for name, start, end in segments:
            if t.is_flash(name):
                key = obs.phase_of(scopes[pr._EVENT_HEAD.match(name).group(0)])
                assert key[1] == "attn"
                flash_by_phase[key[0]] = flash_by_phase.get(key[0], 0) + 1
    assert flash_by_phase == {"fwd": 4, "remat": 4, "bwd": 4}


def test_recorded_text_without_metadata_resolves_to_nothing(recorded):
    """An executable cached before the scopes existed carries none of them:
    the readers then report nothing (the RESOLVED_FLOOR), not a split."""
    trace, scopes, texts = recorded
    stripped = {name: "" for name in scopes}
    assert pr.split_by_scope(trace, stripped, texts) is None
    no_optimizer = {n: ("" if "obs.train.optimizer" in o else o)
                    for n, o in scopes.items()}
    assert pr.split_by_scope(trace, no_optimizer, texts) is None


# -- the benchmark's list -----------------------------------------------------

def test_benchmark_json_lists_the_new_readers_for_the_trainer_cells():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW_DEVICE | NEW_HOST:
        entry = by_name[name]
        assert entry["workloads"] == ["train_mistral_1x8k",
                                      "train_mistral_8x1k"]
        assert entry["better"] == "lower" and entry["unit"] == "ms"
        assert entry["source"] == ("device_trace" if name in NEW_DEVICE
                                   else "program_counter")
        assert entry["moves"] == ("step_ms" if name in NEW_DEVICE
                                  else "tokens_per_s_chip")
    # every reader file of this kind is listed, and nothing else is kept
    readers = {p.stem for p in (ROOT / "chipbench/layer_metrics").glob("*.py")}
    assert readers == set(by_name)
