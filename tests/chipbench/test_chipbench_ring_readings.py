"""The readers of the ring's own scopes (`chipbench/ring_readings.py`,
`layer_metrics/ring_glue_ms_per_step.py`, `ring_hop_wait_ms_per_step.py`):
on a synthetic reading, their None paths, and on traced windows recorded
from the chip with the text of the program that ran (`SAMPLES`)."""

import gzip
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import ring_readings as rr, run, trace as t  # noqa: E402

HERE = Path(__file__).parent
CELL = run.load_cell("ring4_causal_128k")
NEW = ("ring_glue_ms_per_step", "ring_hop_wait_ms_per_step")
BWD = "jit(run)/transpose(jvp())/shard_map/obs.ring.bwd."
SCAN = BWD + "cycle0.scan_rounds/while/body/closed_call/"

# (name, op_name, as the text prints it after the name with `{}` where an
# event's name also has the operand's type, ms a step on the chip)
OPS = [
    ("%burst_flash_fwd.1", "jit(run)/jvp()/shard_map/obs.ring.round0_self/"
     "jit(_fwd_launch)/burst_flash_fwd/pallas_call",
     "(f32[8]{0}) custom-call({}%q)", 200.0),
    ("%multiply_reduce_fusion", BWD + "delta/reduce_sum",
     "f32[8]{0} fusion({}%q), kind=kLoop, calls=%fc.1", 1.5),
    # a scan carry that is also a permute's source: stamped by the compiler
    ("%copy.129", "jit(run)/transpose(jvp())/shard_map",
     "bf16[8]{0} copy({}%q)", 1.9),
    ("%collective-permute-start.3", SCAN + "obs.ring.hop1.sp/ppermute",
     "(bf16[8]{0}, bf16[8]{0}) collective-permute-start({}%copy.129)", 0.1),
    ("%collective-permute-done.3", SCAN + "obs.ring.hop1.sp/ppermute",
     "bf16[8]{0} collective-permute-done({}%collective-permute-start.3)",
     2.0),
    # the dq add: an operand is a permute's, the op is no collective
    ("%add.120", SCAN + "add",
     "f32[8]{0} add({}%collective-permute-done.3, {}%q)", 4.8),
    ("%burst_flash_bwd_rect.5", SCAN + "cond/branch_0_fun/jit(_bwd_launch)/"
     "burst_flash_bwd_rect/pallas_call",
     "(f32[8]{0}) custom-call({}%add.120, {}%broadcast.33)", 300.0),
    # the zeros a kernel accumulates into, named after what it replaced
    ("%broadcast.33", "jit(run)/transpose(jvp())/shard_map/broadcast.10",
     "f32[8]{0} broadcast({}%q)", 1.1),
    ("%convert_element_type.58", BWD + "out/convert_element_type",
     "bf16[8]{0} convert({}%q)", 1.45),
    ("%fusion.9", "jit(run)/mul", "f32[8]{0} fusion({}%q), kind=kLoop, "
     "calls=%fc.9", 0.25),  # the benchmark's loss, outside the ring
]
WANT = {"glue": 1.5 + 1.9 + 4.8 + 1.1 + 1.45, "hop": 2.1,
        "kernel": 500.0, "outside": 0.25}


def hlo_text(ops=OPS):
    lines = ["HloModule jit_run", "",
             "ENTRY %main (q: f32[8]) -> f32[8] {",
             "  %q = f32[8]{0} parameter(0)"]
    for name, op_name, printed, _ in ops:
        meta = f', metadata={{op_name="{op_name}"}}' if op_name else ""
        lines.append(f"  {name} = {printed.replace('{}', '')}{meta}")
    return "\n".join(lines + ["}"])


def ring_reading(monkeypatch, *, text=None, chips=2, steps=2):
    ms = 1_000_000
    segments = {}
    for chip in range(chips):
        rows, cursor = [], 0
        for _ in range(steps):
            for name, _, printed, dur in OPS:
                event = f"{name} = {printed.replace('{}', 'f32[8]{0} ')}"
                rows.append((event, cursor, cursor + int(dur * ms)))
                cursor += int(dur * ms) + 1000
        segments[f"/device:TPU:{chip}"] = rows
    trace = {"devices": segments, "async": {}, "spans": [],
             "window": (0, cursor), "steps": steps}
    monkeypatch.setattr(rr, "op_text",
                        lambda cell, devices: text or hlo_text())
    return {"cell": CELL, "steps": [], "trace": trace}


def read(metric, reading):
    return run.read_layer_metric(CELL, metric, reading)


@pytest.mark.parametrize("kind", sorted(WANT))
def test_each_op_is_one_kind_under_the_part_it_serves(monkeypatch, kind):
    reading = ring_reading(monkeypatch)
    assert rr.ms_per_step(reading, kind) == pytest.approx(WANT[kind])
    split = rr.ms_by_part(reading, kind)
    if kind == "glue":
        assert split == pytest.approx({
            "obs.ring.bwd.delta": 1.5, "obs.ring.bwd.out": 1.45,
            # the stamped copy by the permute it feeds, the stamped zeros
            # by the kernel that reads them
            "obs.ring.bwd.cycle0.scan_rounds": 1.9 + 4.8 + 1.1})
    elif kind == "outside":
        assert split == pytest.approx({None: 0.25})


@pytest.mark.parametrize("metric, kind", zip(NEW, ("glue", "hop")))
def test_the_readers_read_their_kind(monkeypatch, metric, kind):
    assert read(metric, ring_reading(monkeypatch)) == pytest.approx(
        WANT[kind])


def test_the_program_is_compiled_once_for_both_readers(monkeypatch):
    reading = ring_reading(monkeypatch)
    calls = []
    monkeypatch.setattr(rr, "op_text",
                        lambda cell, devices: calls.append(1) or hlo_text())
    for metric in NEW:
        read(metric, reading)
    assert calls == [1]


@pytest.mark.parametrize("metric", NEW)
@pytest.mark.parametrize("why", ["untraced", "no_device_plane",
                                 "not_an_op_cell", "no_bwd_scopes",
                                 "another_shape", "another_operand"])
def test_the_readers_give_nothing_not_zero(monkeypatch, metric, why):
    reading = ring_reading(monkeypatch)
    if why == "untraced":
        reading["trace"] = None
    elif why == "no_device_plane":  # the CPU rehearsal
        reading["trace"]["devices"] = {}
    elif why == "not_an_op_cell":
        reading["cell"] = run.load_cell("train_mistral_8x1k")
    elif why == "no_bwd_scopes":  # the parent of this PR
        reading = ring_reading(monkeypatch, text=hlo_text().replace(
            "obs.ring.bwd.", "obs.ring."))
    else:  # every name joins, and one is not the instruction that ran
        other = {"another_shape": ("bf16[8]{0} copy", "bf16[16]{0} copy"),
                 "another_operand": ("add(%collective-permute-done.3",
                                     "add(%copy.129")}[why]
        assert other[0] in hlo_text()
        reading = ring_reading(monkeypatch,
                               text=hlo_text().replace(*other))
    assert read(metric, reading) is None


def test_a_branch_s_root_is_placed_by_its_conditional():
    """A stamped op whose only user is its computation's ROOT (a dead
    round's zeros) takes the scope of the conditional that runs it."""
    text = "\n".join([
        "HloModule jit_run", "",
        "%dead (p: f32[8]) -> (f32[8]) {",
        "  %p = f32[8]{0} parameter(0)",
        '  %wrapped_broadcast.1 = f32[8]{0} fusion(%p), kind=kLoop, '
        'calls=%wb, metadata={op_name="jit(run)/transpose(jvp())/'
        'shard_map/broadcast.30"}',
        "  ROOT %tuple.42 = (f32[8]{0}) tuple(%wrapped_broadcast.1)",
        "}", "",
        "ENTRY %main (q: f32[8], i: s32[]) -> (f32[8]) {",
        "  %q = f32[8]{0} parameter(0)",
        "  %i = s32[] parameter(1)",
        "  ROOT %conditional.4 = (f32[8]{0}) conditional(%i, %q, %q), "
        "branch_computations={%dead, %dead}, "
        f'metadata={{op_name="{BWD}cycle0.last_round/cond"}}',
        "}"])
    placed = rr.placed_scopes(text)
    assert placed["%wrapped_broadcast.1"] == BWD + "cycle0.last_round/cond"


def test_benchmark_json_lists_the_ring_readers():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    by_name = {m["name"]: m for m in spec["per_layer"]}
    assert [m["name"] for m in spec["per_layer"][-2:]] == list(NEW)
    assert by_name["ring_glue_ms_per_step"]["workloads"] == [
        "ring4_causal_128k", "op_causal_64k"]
    assert by_name["ring_hop_wait_ms_per_step"]["workloads"] == [
        "ring4_causal_128k"]
    for name in NEW:
        entry = by_name[name]
        assert (entry["layer"], entry["source"], entry["moves"],
                entry["unit"], entry["better"]) == (
            "ring", "device_trace", "step_ms", "ms", "lower")


# -- traced windows recorded on the chip ----------------------------------------

SAMPLES = {"op_causal_64k": ("ring_events_sample_op64k.json.gz",
                             "op_hlo_sample_op64k.txt.gz"),
           "ring4_causal_128k": ("ring_events_sample_ring4.json.gz",
                                 "ring_hlo_sample_ring4.txt.gz")}


def recorded(cell):
    """(the reduced trace of one chip's five traced steps, the op program's
    text compiled on that chip, what the chip run read)."""
    events, text = SAMPLES[cell]
    with gzip.open(HERE / events, "rt") as f:
        raw = json.load(f)
    with gzip.open(HERE / text, "rt") as f:
        text = f.read()
    return t.reduce_trace(raw, 5), text, raw


@pytest.mark.parametrize("cell", sorted(SAMPLES))
def test_recorded_window_reads_what_the_chip_read(cell):
    trace, text, raw = recorded(cell)
    reading = {"cell": run.load_cell(cell), "trace": trace}
    joined = rr.join(trace, text)
    assert joined is not None
    reading["ring_segments"] = joined
    for kind, split in raw["chip0"].items():
        if kind in rr.KINDS:
            assert rr.ms_by_part(reading, kind) == pytest.approx(
                split, rel=1e-12, abs=1e-12), kind
    # a one-chip cell's one chip is the result line's mean, to the digit
    if reading["cell"]["chips"] == 1:
        assert rr.ms_per_step(reading, "glue") == pytest.approx(
            raw["reported"]["ring_glue_ms_per_step"], rel=1e-12)
    # nothing the device ran that step is outside the ring's scopes
    assert rr.ms_per_step(reading, "outside") == 0


@pytest.mark.parametrize("cell", sorted(SAMPLES))
def test_recorded_window_on_a_text_without_the_backward_s_scopes(cell):
    """The parent's program carries no `obs.ring.bwd.` scope: the readers
    read nothing, not a number."""
    trace, text, _ = recorded(cell)
    assert rr.join(trace, text.replace("obs.ring.bwd.", "obs.ring.")) is None


@pytest.mark.parametrize("cell", sorted(SAMPLES))
def test_recorded_window_every_event_is_the_text_s_instruction(cell):
    trace, text, _ = recorded(cell)
    texts = rr.p.instruction_texts(text)
    names = {n for segs in trace["devices"].values() for n, _, _ in segs}
    heads = {rr.p._EVENT_HEAD.match(n).group(0) for n in names}
    assert heads <= set(texts)
    assert all(rr.p.same_instruction(
        n, texts[rr.p._EVENT_HEAD.match(n).group(0)]) for n in names)


def test_recorded_ring_window_kinds_partition_the_busy_time():
    """Kernels, hop waits and glue are the chip's busy time, exactly; and
    the outside `exposed_permute_ms_per_step` is the waits plus the glue
    ops whose event names mention a permute as an operand (the `dq` adds
    and its copy out): it matches the whole event name."""
    trace, text, raw = recorded("ring4_causal_128k")
    reading = {"cell": CELL, "trace": trace,
               "ring_segments": rr.join(trace, text)}
    kinds = {k: rr.ms_per_step(reading, k) for k in rr.KINDS}
    busy = 1e3 * t.busy_seconds(trace) / trace["steps"]
    assert sum(kinds.values()) == pytest.approx(busy, rel=1e-9)
    mentioned = sum(e - s for segs in trace["devices"].values()
                    for n, s, e in segs if t.is_permute(n)
                    and not n.startswith("%collective-permute")) * 1e-6 / 5
    exposed = read("exposed_permute_ms_per_step", {"trace": trace})
    # to a microsecond a step: a transfer in flight while the chip idles
    assert exposed == pytest.approx(kinds["hop"] + mentioned, abs=1e-3)
    assert mentioned > kinds["hop"] > 1.0
