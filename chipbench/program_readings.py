"""What the readers of the PROGRAM's own instrumentation share (the readers
of the benchmark's spans and of plain event names share `trace.py`).

Device side.  A profiler event's name is the instruction as the compiler
prints it (`%fusion.388 = bf16[...] fusion(...), kind=kOutput, calls=...`)
and carries no metadata (PERF.md, PR 25), so the way from an event to the
scope it was traced under is the executable's text: `device_ms` rebuilds the
step's shapes from the cell as `runners/train.py` does, compiles the step
again (a cache read from a checkout's second traced run on) and joins
`obs.spans.scope_map(as_text())` on the name at the head of each event.
The rebuilt program is another object than the one that was traced, so each
event is first held against the text's instruction of its name (result
type, opcode, operands, fusion kind: `same_instruction`); one that differs
means another program, and no device metric is reported.
`obs.spans.phase_of` then reads phase and module out of the op_name.

Host side.  `make_train_step` leaves one `train.step` span a step in the
program's span ring, dispatch to dispatch; `window_records` picks the
untraced window's.

Every function returns None, and never raises or returns 0, where the
program has no such instrumentation (the parent of the PR that added it).
"""

import re
import statistics

import jax
import numpy as np

from burst_attn_tpu import obs

from . import trace as t

try:
    from burst_attn_tpu.obs.spans import (
        instruction_texts, phase_of, scope_map)
except ImportError:  # a program from before the scopes: nothing to read
    instruction_texts = phase_of = scope_map = None

RESOLVED_FLOOR = 0.95  # of the non-flash device time, or no device metric
DISPATCH_AGREE_S = 2e-3  # a record against the benchmark's own clock
STEP_SPAN = "train.step"
_EVENT_HEAD = re.compile(r"%[^\s=]+")
_REF = re.compile(r"%[\w.\-]+")
_OPCODE = re.compile(r" [a-z][\w\-]*\(")
_FUSION_KIND = re.compile(r"\bkind=\w+")


def step_text(cell, devices):
    """`as_text()` of the cell's step program, compiled for `devices` from
    shapes alone, as `runners.train.Session` sizes it."""
    from burst_attn_tpu.models import train
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .runners.train import model_config

    model, mix = cell["config"], cell["traffic"]
    cfg, tcfg = model_config(model), train.TrainConfig()
    mesh = train.make_mesh({"sp": mix["sp"]}, devices=devices[:mix["sp"]])
    shapes = jax.eval_shape(
        lambda key: train.init_train_state(key, cfg, tcfg, mesh),
        jax.random.PRNGKey(0))
    specs = train.state_specs(cfg, tcfg, shapes[0])
    state = jax.tree.map(
        lambda spec, x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec)),
        specs, shapes, is_leaf=lambda x: isinstance(x, P))
    tokens = np.zeros((mix["batch"], mix["seq"]), np.int32)
    batch = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=a.sharding),
        train.batch_from_host(tokens, tokens, cfg, mesh))
    return train.jit_train_step(cfg, tcfg, mesh).lower(
        state, batch).compile().as_text()


def _signature(printed):
    """(result type and opcode, the instructions named in order, fusion
    kind) of a printed instruction; the same with operand types (an
    event's name) and without (the executable's text)."""
    opcode = _OPCODE.search(printed)
    cut = opcode.end() if opcode else len(printed)
    return (printed[:cut], _REF.findall(printed[cut:]),
            _FUSION_KIND.findall(printed))


def same_instruction(event_name, text):
    """Whether a profiler event (`%fusion.388 = <type> fusion(<typed
    operands>), kind=..., calls=...`) prints the instruction that the
    executable's text has under that name (`obs.instruction_texts`)."""
    return _signature(event_name.split(" = ", 1)[-1]) == _signature(text)


def split_by_scope(trace, scopes, texts):
    """{(phase, module): ms per step} of the reduced trace's device self
    time, mean over chips, every segment charged to exactly one key; None
    where an event is not the instruction `texts` has under its name (the
    text is another program's), or where under RESOLVED_FLOOR of the
    non-flash time has a phase."""
    sums, non_flash, lost, checked = {}, 0, 0, {}
    for segments in trace["devices"].values():
        for name, start, end in segments:
            head = _EVENT_HEAD.match(name)
            head = head.group(0) if head else ""
            if name not in checked:
                checked[name] = (head not in texts
                                 or same_instruction(name, texts[head]))
                if not checked[name]:
                    return None
            key = phase_of(scopes.get(head, ""))
            sums[key] = sums.get(key, 0) + end - start
            if not t.is_flash(name):
                non_flash += end - start
                lost += end - start if key[0] == "other" else 0
    if not non_flash or lost > (1 - RESOLVED_FLOOR) * non_flash:
        return None
    scale = 1e-6 / len(trace["devices"]) / trace["steps"]
    return {key: ns * scale for key, ns in sums.items()}


def device_ms(reading):
    """split_by_scope of the run's traced window, worked out once a run
    (the readers of one run share `reading`); None without a device trace
    or without scopes in the program."""
    if "program_device_ms" not in reading:
        trace, out = t.traced(reading), None
        if trace is not None and scope_map is not None:
            text = step_text(reading["cell"], jax.devices())
            out = split_by_scope(trace, scope_map(text),
                                 instruction_texts(text))
        reading["program_device_ms"] = out
    return reading["program_device_ms"]


def scope_ms(reading, *, phase=None, module=None):
    """Device ms a step of the ops of `phase` and of `module` (None: every
    one); None where `device_ms` is."""
    split = device_ms(reading)
    if split is None:
        return None
    return sum(ms for (p, m), ms in split.items()
               if phase in (None, p) and module in (None, m))


def window_records(reading):
    """The `train.step` spans of the untraced window's steps but the last,
    oldest first; None where the ring does not hold them or they are not
    the window's.

    The ring holds warm-up, the window's n steps and the k traced steps in
    dispatch order.  A step's span closes at the NEXT dispatch, so the last
    traced step has none and the closed ones end `n + k - 1` before the end
    with the window's first.  The window's last runs across the profiler's
    start and is left out.  A record runs from its step's dispatch to the
    next one's, which the benchmark's own records give as well (a dispatch
    is `step_s` before its step is done, and `interval_s` lies between two
    steps' ends); where the two disagree the count is off and nothing is
    returned."""
    steps = reading["steps"]
    traced_steps = reading["trace"]["steps"] if reading["trace"] else 0
    spans = [s for s in obs.completed_spans() if s.name == STEP_SPAN]
    since_window = len(steps) + traced_steps - 1
    if len(steps) < 2 or len(spans) < since_window:
        return None  # no such spans, or the ring wrapped inside the window
    records = spans[-since_window:][:len(steps) - 1]
    seqs = [r.attrs.get("seq") for r in records]
    if seqs != list(range(seqs[0], seqs[0] + len(seqs))):
        return None
    for record, step, after in zip(records, steps, steps[1:]):
        to_next = after["interval_s"] - after["step_s"] + step["step_s"]
        if abs(record.duration_s - to_next) > DISPATCH_AGREE_S:
            return None
    return records


def mean_attr_ms(reading, attr):
    """Mean over the window's records of the attr `attr` (seconds), in ms;
    None where any record lacks it (a platform without that counter)."""
    records = window_records(reading)
    if records is None or any(attr not in r.attrs for r in records):
        return None
    return 1e3 * statistics.fmean(r.attrs[attr] for r in records)
