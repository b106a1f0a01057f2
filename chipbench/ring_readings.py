"""What the readers of the ring's own scopes share
(layer_metrics/ring_glue_ms_per_step.py, ring_hop_wait_ms_per_step.py).

`parallel/burst.py` puts every op of the ring's forward, backward and the
gradients' casts under an `obs.ring.*` scope: the part of the schedule it
serves (`obs.ring.round0_self`, `obs.ring.bwd.cycle0.scan_rounds`, ...), with
`obs.ring.hop<h>.<axis>` nested inside around each permute.  A profiler event
carries no metadata, so the way from an event to its scope is the op
program's text, as for the trainer (`program_readings.py`): `op_text`
rebuilds the op cells' program from the cell as `runners/op.py` builds it
(that runner keeps no text), every event is held against the text's
instruction of its name (`program_readings.same_instruction`) and joined to
its op_name by `obs.scope_map`.

The compiler stamps some instructions it makes inside the ring with the
shard_map's own op_name, or with that of an instruction it replaced (`.../
shard_map`, `.../shard_map/broadcast.10`): the copies of a scan's carries
that are also a permute's source, the zeros a kernel accumulates into, a
conditional it rewrote.  Such an instruction is placed as `obs.scope_map`
places one without metadata: by the nearest instruction that uses its
result, else by the nearest that makes its operands, here the nearest that
holds an `obs.ring.` scope.

Every function returns None, and never raises, where there is no device
trace, the cell's runner is not `op`, an event is not the text's
instruction of its name, or no op carries an `obs.ring.bwd.` scope (a
program from before the backward's scopes).
"""

import re

from . import program_readings as p, trace as t

RING = "obs.ring."
_PART = re.compile(r"obs\.ring\.[\w.\-]+")
# an op_name that ends at the shard_map, or in an instruction's name
_STAMPED = re.compile(r"(?:^|/)(?:shard_map|[a-z][\w\-]*\.\d+)$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?(%[^\s(]+)\s*\(.*\{\s*$")
_ROOT = re.compile(r"^\s+ROOT\s+(%[^\s=]+)\s*=")
KINDS = ("kernel", "hop", "glue", "outside")


def op_text(cell, devices):
    """`as_text()` of the op cells' program (`runners/op.py`: forward and
    backward of `burst_attn` under the reference's loss, compiled from the
    inputs' shapes and sharding), compiled for `devices` from shapes
    alone."""
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import burst_attn_tpu as bat

    cfg, mix = cell["config"], cell["traffic"]
    reference = importlib.import_module(
        f"chipbench.references.{cfg['reference']}")
    mesh = Mesh(np.array(devices[:mix["sp"]]), ("sp",))
    sharding = NamedSharding(mesh, P(None, None, "sp", None))

    def shape(heads):
        return jax.ShapeDtypeStruct(
            (mix["batch"], heads, mix["seq"], cfg["head_dim"]),
            jnp.dtype(cfg["dtype"]), sharding=sharding)

    def attn(q, k, v):
        return bat.burst_attn(q, k, v, mesh=mesh, causal=cfg["causal"],
                              layout=cfg["layout"], backend=cfg["backend"])

    q, kv = shape(cfg["num_attention_heads"]), shape(
        cfg["num_key_value_heads"])
    return jax.jit(reference.fwd_bwd(attn)).lower(
        q, kv, kv, q).compile().as_text()


def placed_scopes(text):
    """`obs.scope_map(text)` with each instruction whose op_name the
    compiler stamped given the op_name of the nearest user, else operand,
    that holds an `obs.ring.` scope (left as it was where none does).  A
    computation's ROOT is used by the instructions that call the
    computation (a conditional's branch by the conditional)."""
    scopes, texts = p.scope_map(text), p.instruction_texts(text)
    operands, roots, computation = {}, {}, None
    for line in text.splitlines():
        opened = _COMPUTATION.match(line)
        if opened:
            computation = opened.group(1)
        head = _ROOT.match(line)
        if head:
            roots[computation] = head.group(1)
    users = {}
    for name, printed in texts.items():
        operands[name] = [r for r in p._REF.findall(printed)
                          if r in texts and r != name]
        for ref in operands[name]:
            users.setdefault(ref, []).append(name)
        for called in p._REF.findall(printed):
            if called in roots:
                users.setdefault(roots[called], []).append(name)

    def nearest(start, edges):
        seen, frontier = {start}, [start]
        while frontier:
            nxt = []
            for node in frontier:
                for other in edges.get(node, ()):
                    if other in seen:
                        continue
                    if RING in scopes.get(other, ""):
                        return scopes[other]
                    seen.add(other)
                    nxt.append(other)
            frontier = nxt
        return None

    out = dict(scopes)
    for name, op_name in scopes.items():
        if RING not in op_name and (not op_name or _STAMPED.search(op_name)):
            out[name] = (nearest(name, users) or nearest(name, operands)
                         or op_name)
    return out


def join(trace, text):
    """{plane: [(part, kind, ns)]} of the trace's device self time, joined
    to the instructions of the program's `text`: `part` the outermost
    `obs.ring.*` scope of the instruction's op_name (None outside every
    one), `kind` one of KINDS (a `burst_flash_*` kernel; a collective on the
    core, which in the ring is a permute's `-start` or `-done`; any other
    op under a ring scope; an op under none); None where an event is not
    the text's instruction of its name, or where no op carries
    `obs.ring.bwd.`."""
    if RING + "bwd." not in text:
        return None
    scopes, texts = placed_scopes(text), p.instruction_texts(text)
    out, known = {}, {}
    for plane, segments in trace["devices"].items():
        rows = out[plane] = []
        for name, start, end in segments:
            head = p._EVENT_HEAD.match(name)
            head = head.group(0) if head else ""
            if name not in known:
                if head in texts and not p.same_instruction(name, texts[head]):
                    return None
                known[name] = _classify(head, scopes.get(head, ""),
                                        texts.get(head, ""))
            rows.append((*known[name], end - start))
    return out


def _classify(head, op_name, text):
    part = _PART.search(op_name)
    if part is None:
        return None, "outside"
    opcode = p._OPCODE.search(text)
    opcode = opcode.group(0).strip(" (") if opcode else ""
    if t.is_flash(head):
        return part.group(0), "kernel"
    return part.group(0), "hop" if t.is_collective(opcode) else "glue"


def segments(reading):
    """`join` of the run's traced window, worked out once a run (the
    readers of one run share `reading`); None where the run has no device
    trace or is not an op cell."""
    if "ring_segments" not in reading:
        trace, out = t.traced(reading), None
        if (trace is not None and p.scope_map is not None
                and reading["cell"]["config"].get("runner") == "op"):
            import jax

            out = join(trace, op_text(reading["cell"], jax.devices()))
        reading["ring_segments"] = out
    return reading["ring_segments"]


def ms_by_part(reading, kind):
    """{part: device self ms a step, mean over chips} of the ops of `kind`;
    None where `segments` is."""
    planes = segments(reading)
    if planes is None:
        return None
    sums = {}
    for rows in planes.values():
        for part, k, ns in rows:
            if k == kind:
                sums[part] = sums.get(part, 0) + ns
    scale = 1e-6 / len(planes) / reading["trace"]["steps"]
    return {part: ns * scale for part, ns in sums.items()}


def ms_per_step(reading, kind):
    """Device self ms a step, mean over chips, of the ops of `kind`; None
    where `segments` is."""
    split = ms_by_part(reading, kind)
    return None if split is None else sum(split.values())
