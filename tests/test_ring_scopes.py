"""The ring's `obs.ring.*` scopes in the compiled op program (forward and
backward of `burst_attn` under the op cells' loss, on simulated CPU
devices): every op of the ring carries one and they add no instruction.  The
schedule's rounds, hops and payload bytes are read off the program by them in
tests/test_obs.py and tests/test_wire_quant.py.

The readers of the benchmark's ring metrics (`chipbench/ring_readings.py`)
join a profiler trace to these op_names; what they read on the chip is
tested on a recorded sample in `tests/chipbench/`.
"""

import contextlib
import functools
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import burst_attn_tpu as bat
from burst_attn_tpu import obs

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import ring_readings  # noqa: E402
from chipbench.references import dense_attention  # noqa: E402

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?(%[^\s(]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?(%[^\s=]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
_CONTROL = re.compile(r"\b(?:body|condition|true_computation|"
                      r"false_computation)=(%[\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_TRIP = re.compile(r'"known_trip_count":\{"n":"(\d+)"')
# what a device runs no op for
_NO_OP = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}
_BYTES = {"s8": 1, "u8": 1, "f8e4m3fn": 1, "bf16": 2, "f16": 2, "f32": 4,
          "s32": 4, "u32": 4}

# the op cells' configuration at a CPU's size, and variants of the ring
ZIGZAG = dict(causal=True, layout="zigzag")
VARIANTS = {
    "zigzag": ({"sp": 4}, ZIGZAG),
    "double_ring": ({"inter": 2, "intra": 4}, ZIGZAG),
    "wire_int8": ({"sp": 4}, dict(ZIGZAG, wire_dtype="int8")),
    "contig_window": ({"sp": 4}, dict(causal=True, layout="contig",
                                      window=24)),
}


def executed_ops(text):
    """[(name, opcode, times a step runs it)] of the instructions a device
    runs as ops: the entry computation's and those of the computations its
    loops and conditionals run (not a fusion's insides, not a reduction's
    body); an op in a loop's body runs the loop's known trip count times."""
    computations, current, entry = {}, None, None
    for line in text.splitlines():
        opened = _COMPUTATION.match(line)
        if opened:
            current = opened.group(1)
            computations[current] = []
            entry = current if line.startswith("ENTRY") else entry
            continue
        head = _INSTRUCTION.match(line)
        if head and current:
            computations[current].append(head.groups())
    out, todo, seen = [], [(entry, 1)], set()
    while todo:
        computation, times = todo.pop()
        if computation in seen:
            continue
        seen.add(computation)
        for name, rest in computations[computation]:
            opcode = _OPCODE.search(rest)
            opcode = opcode.group(1) if opcode else ""
            out.append((name, opcode, times))
            trip = _TRIP.search(rest)
            for called in _CONTROL.findall(rest):
                todo.append((called, times * int(trip.group(1))
                             if trip and "body=" + called in rest else times))
            for branches in _BRANCHES.findall(rest):
                todo += [(b, times) for b in re.findall(r"%[\w.\-]+",
                                                        branches)]
    return out


def op_program(axes, kw, *, seq_per_dev=32, heads=2, d=16,
               dtype=jnp.bfloat16, grad=True):
    """as_text() of the op cells' program (dense_attention.fwd_bwd over
    burst_attn) on a mesh of `axes`, the sequence sharded over all of
    them."""
    names = tuple(axes)
    mesh = Mesh(np.array(jax.devices()[:int(np.prod(list(axes.values())))])
                .reshape(tuple(axes.values())), names)
    seq_axes = names if len(names) > 1 else names[0]
    sharding = NamedSharding(mesh, P(None, None, seq_axes, None))
    x = jax.ShapeDtypeStruct((1, heads, seq_per_dev * mesh.size, d), dtype,
                             sharding=sharding)

    def attn(q, k, v):
        return bat.burst_attn(q, k, v, mesh=mesh, seq_axes=names,
                              backend="jnp", **kw)

    fn = dense_attention.fwd_bwd(attn) if grad else attn
    args = (x, x, x, x) if grad else (x, x, x)
    return jax.jit(fn).lower(*args).compile().as_text()


def ring_op_names(text):
    """{op: its op_name as the ring's readers place it} over executed_ops."""
    placed = ring_readings.placed_scopes(text)
    return {name: placed[name] for name, opcode, _ in executed_ops(text)
            if opcode not in _NO_OP}


@functools.lru_cache(maxsize=None)
def program(variant):
    return op_program(*VARIANTS[variant])


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_every_op_of_the_ring_carries_a_ring_scope(variant):
    """Every op the device runs inside the shard_map is under an
    `obs.ring.*` scope; only the loss around it (`sum(o * do)` and its
    cotangent, outside the shard_map) may be under none."""
    names = ring_op_names(program(variant))
    outside = {n: o for n, o in names.items()
               if "obs.ring." not in o and re.search(r"shard_map/", o)}
    assert not outside and len(names) > 50, outside
    # and every part of the schedule is there, forward and backward
    parts = {m.group(0) for o in names.values()
             for m in [ring_readings._PART.search(o)] if m}
    want = {"obs.ring.round0_self", "obs.ring.cycle0.scan_rounds",
            "obs.ring.cycle0.last_round", "obs.ring.finalize",
            "obs.ring.bwd.delta", "obs.ring.bwd.round0_self",
            "obs.ring.bwd.cycle0.send0", "obs.ring.bwd.return_home",
            "obs.ring.bwd.out"}
    if variant == "double_ring":
        want |= {"obs.ring.cycle1.last_round",
                 "obs.ring.bwd.cycle1.first_round",
                 "obs.ring.bwd.cycle1.last_round"}
    if variant == "contig_window":  # two live rounds: no scan
        want -= {"obs.ring.cycle0.scan_rounds"}
    else:
        want |= {"obs.ring.bwd.cycle0.scan_rounds",
                 "obs.ring.bwd.cycle0.last_round"}
    assert want <= parts, want - parts


def test_the_op_cells_program_rebuilt_from_the_cell_is_scoped_alike():
    """`ring_readings.op_text` builds the program `runners/op.py` runs from
    the cell alone; on four CPU devices its ops are scoped as the test's
    own build of it."""
    cell = {"config": {"reference": "dense_attention",
                       "num_attention_heads": 2, "num_key_value_heads": 2,
                       "head_dim": 16, "dtype": "bfloat16", "causal": True,
                       "layout": "zigzag", "backend": "jnp"},
            "traffic": {"batch": 1, "seq": 128, "sp": 4}}
    text = ring_readings.op_text(cell, jax.devices())
    names = ring_op_names(text)
    assert not [o for o in names.values()
                if "obs.ring." not in o and re.search(r"shard_map/", o)]
    assert sum("obs.ring.bwd." in o for o in names.values()) > 20


def _strip(text):
    """The program's instructions without their metadata, without the
    tables of source locations the metadata points into, and with every
    instruction and computation named by the order it first appears in:
    the uniquifier numbers some names otherwise when the name stack holds
    scopes (`%broadcast_in_dim.400` for `.391`), the same instruction."""
    text = re.sub(r",?\s*metadata=\{[^}]*\}", "", text)
    text = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r"(?:.+\n)*", "\n", text)
    names = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%{len(names)}"),
                  text)


@pytest.mark.parametrize("variant", ["zigzag", "double_ring"])
def test_the_scopes_add_no_instruction(variant, monkeypatch):
    scoped = program(variant)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = op_program(*VARIANTS[variant])
    assert "obs.ring." not in bare and "obs.ring.bwd." in scoped
    assert _strip(bare) == _strip(scoped)


def permutes(text):
    """[(op_name, bytes, times a step runs it)] of the collective-permutes
    the device runs."""
    texts, scopes = obs.instruction_texts(text), obs.scope_map(text)
    out = []
    for name, opcode, times in executed_ops(text):
        if opcode.startswith("collective-permute"):
            dtype, dims = re.match(r"\(?(\w+)\[([\d,]*)\]",
                                   texts[name]).groups()
            size = int(np.prod([int(x) for x in dims.split(",") if x]))
            out.append((scopes[name], size * _BYTES[dtype], times))
    return out
