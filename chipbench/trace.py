"""From a profiler trace to numbers: the one reduction every PR is read by.

`read_xplane` turns the profiler's `.xplane.pb` into plain lists (the raw
events of the device's op lines and the benchmark's host spans, one clock,
nanoseconds); `reduce_trace` cuts them to the traced window and resolves
nesting into disjoint self-time segments.  The functions below it are what
the per-layer readers (`layer_metrics/*.py`) and the breakdown are made of.
All of it is plain Python on lists, checked on a recorded event list in
tests/chipbench.
"""

import bisect
import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OP_LINE = "XLA Ops"  # the core's stream of ops, one at a time
ASYNC_LINE = "Async XLA Ops"  # transfers in flight beside it
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
BLOCK_SPAN = "bench.block"  # ends when the device work it waited for is done

FLASH_KERNEL = "burst_flash_"
PERMUTE = "collective-permute"
COLLECTIVES = (PERMUTE, "all-reduce", "all-gather", "reduce-scatter",
               "all-to-all")


def read_xplane(trace_dir):
    """{"devices": {plane: [[[name, start_ns, dur_ns], ...] per op line]},
    "async": the same of the async lines, "spans": [[name, start_ns,
    dur_ns], ...], "lines": {plane: [line names]}} of the newest trace under
    `trace_dir`."""
    import jax

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    profile = jax.profiler.ProfileData.from_file(paths[-1])
    devices, in_flight, spans, lines = {}, {}, [], {}
    for plane in profile.planes:
        lines[plane.name] = [line.name for line in plane.lines]
        if plane.name.startswith(DEVICE_PLANE):
            for kept, wanted in ((devices, OP_LINE), (in_flight, ASYNC_LINE)):
                kept[plane.name] = [
                    [[e.name, e.start_ns, e.duration_ns] for e in line.events]
                    for line in plane.lines if line.name == wanted]
        elif plane.name == HOST_PLANE:
            spans += [[e.name, e.start_ns, e.duration_ns]
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "async": in_flight, "spans": spans,
            "lines": lines}


def self_segments(events):
    """The events of ONE line as disjoint (name, start, end) pieces: each
    event's interval less the events nested in it, so that a `while` that
    spans its body is charged only what no body op covers."""
    out, stack = [], []  # stack entries: [name, end, cursor]

    def close(entry):
        name, end, cursor = entry
        if end > cursor:
            out.append((name, cursor, end))

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        if stack:
            parent = stack[-1]
            if start > parent[2]:
                out.append((parent[0], parent[2], start))
            parent[2] = max(parent[2], min(end, parent[1]))
        stack.append([name, end, start])
    while stack:
        close(stack.pop())
    return sorted(out, key=lambda s: s[1])


def union(intervals):
    """Sorted, merged (start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        elif end > start:
            merged.append([start, end])
    return [tuple(m) for m in merged]


def total(intervals):
    return sum(end - start for start, end in intervals)


def subtract(a, b):
    """The parts of the union of `a` that the union of `b` does not cover."""
    out, b = [], union(b)
    for start, end in union(a):
        cursor = start
        for bs, be in b:
            if be <= cursor:
                continue
            if bs >= end:
                break
            if bs > cursor:
                out.append((cursor, bs))
            cursor = max(cursor, be)
        if cursor < end:
            out.append((cursor, end))
    return out


def device_clock_shift(raw):
    """Nanoseconds to add to the device planes' times to put them on the
    host plane's clock.  The profiler's two clocks differ by milliseconds
    (2.2-2.6 ms early on the v5e, PERF.md PR 24), which matters only for
    saying what the host was doing in an idle gap.  Causality bounds the
    shift: no device op ends after the `bench.block` span that waited for it
    returns.  The shift is the largest that rule allows (the host notices
    completion faster than it launches), 0 without such spans."""
    ends = sorted(s + d for lines in raw["devices"].values()
                  for line in lines for _, s, d in line)
    shifts = []
    for name, start, dur in raw["spans"]:
        done = bisect.bisect_right(ends, start + dur)
        if name == BLOCK_SPAN and done:
            shifts.append(start + dur - ends[done - 1])
    return min(shifts) if shifts else 0


def reduce_trace(raw, steps):
    """Put the device events on the host's clock, cut them to the
    `bench.window` span and resolve nesting: {"devices": {plane: [(name,
    start, end), ...]}, "async": the same of the transfers in flight,
    "spans": [(name, start, end), ...], "window": (t0, t1), "steps": steps,
    "device_clock_shift": ns}, nanoseconds."""
    windows = [s for s in raw["spans"] if s[0] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"want one {WINDOW_SPAN} span in the trace, found "
                         f"{len(windows)}")
    t0 = windows[0][1]
    t1 = t0 + windows[0][2]
    shift = device_clock_shift(raw)

    def cut(planes):
        out = {}
        for plane, lines in planes.items():
            segs = [(n, s + shift, e + shift)
                    for line in lines for n, s, e in self_segments(line)]
            out[plane] = sorted(
                ((n, max(s, t0), min(e, t1)) for n, s, e in segs
                 if min(e, t1) > max(s, t0)), key=lambda s: s[1])
        return out

    devices = cut(raw["devices"])
    spans = [(n, max(s, t0), min(s + d, t1)) for n, s, d in raw["spans"]
             if n != WINDOW_SPAN and min(s + d, t1) > max(s, t0)]
    return {"devices": devices, "async": cut(raw.get("async", {})),
            "spans": sorted(spans, key=lambda s: s[1]),
            "window": (t0, t1), "steps": steps, "device_clock_shift": shift}


def is_flash(name):
    return FLASH_KERNEL in name


def is_collective(name):
    return any(c in name for c in COLLECTIVES)


def is_permute(name):
    return PERMUTE in name


def traced(reading):
    """The run's reduced trace if it holds a device's ops, else None: what
    every reader of the device trace starts from."""
    trace = reading["trace"]
    return trace if trace is not None and trace["devices"] else None


def seconds_where(segments, pred):
    """Self time, in seconds, of one device's segments whose name passes."""
    return sum(e - s for n, s, e in segments if pred(n)) * 1e-9


def mean_over_devices(trace, fn):
    """The mean over the traced chips of fn(segments); None with no chip."""
    per_device = [fn(segs) for segs in trace["devices"].values()]
    return sum(per_device) / len(per_device) if per_device else None


def flash_seconds(trace):
    """Self seconds of the `burst_flash_*` kernels, mean over the chips."""
    return mean_over_devices(trace, lambda segs: seconds_where(segs, is_flash))


def busy_intervals(segments):
    return union((s, e) for _, s, e in segments)


def busy_seconds(trace):
    """Seconds in which any op ran on the chip, averaged over the chips."""
    return mean_over_devices(
        trace, lambda segs: total(busy_intervals(segs)) * 1e-9)


def window_seconds(trace):
    return (trace["window"][1] - trace["window"][0]) * 1e-9


def permute_seconds(trace):
    """(seconds in which a collective-permute ran or was in flight on a
    chip, the part of them in which no op that is not a collective ran
    there).  The permutes are taken from both lines: the core's `-start`
    and `-done` ops (a `-done` that waits is exposed time) and the transfers
    in flight beside them.  The profiler writes the transfers of the first
    chip only (PERF.md, PR 24), so where any chip has them the mean is over
    those chips, and over all chips where none has."""
    planes = ([p for p in trace["devices"] if trace["async"].get(p)]
              or list(trace["devices"]))
    sums = [0.0, 0.0]
    for plane in planes:
        segs = trace["devices"][plane]
        flying = [(s, e) for n, s, e in segs + trace["async"].get(plane, [])
                  if is_permute(n)]
        compute = [(s, e) for n, s, e in segs if not is_collective(n)]
        sums[0] += total(union(flying)) * 1e-9
        sums[1] += total(subtract(flying, compute)) * 1e-9
    n = max(len(planes), 1)
    return sums[0] / n, sums[1] / n


def display_name(name):
    """A flash kernel under its stable name, whichever call of it this is;
    any other op under the name the trace gives it, cut to 96 characters."""
    kernel = re.search(FLASH_KERNEL + r"[a-z_]+", name)
    return kernel.group(0) if kernel else name[:96]


def seconds_by_name(trace):
    """Self seconds under every `display_name`, averaged over the chips."""
    sums = {}
    for segs in trace["devices"].values():
        for name, start, end in segs:
            name = display_name(name)
            sums[name] = sums.get(name, 0.0) + (end - start) * 1e-9
    n = max(len(trace["devices"]), 1)
    return {name: s / n for name, s in sums.items()}


def idle_gaps_by_span(trace):
    """The window's idle time on the chip, split by the benchmark span that
    was open on the host meanwhile ("(no span)" for the rest), averaged over
    the chips: {span name: seconds}."""
    by_name = {}
    for name, start, end in trace["spans"]:
        by_name.setdefault(name, []).append((start, end))
    sums = {}
    for segs in trace["devices"].values():
        gaps = subtract([trace["window"]], busy_intervals(segs))
        idle = left = total(gaps)
        for name, intervals in by_name.items():
            covered = idle - total(subtract(gaps, intervals))
            sums[name] = sums.get(name, 0.0) + covered * 1e-9
            left -= covered
        sums["(no span)"] = sums.get("(no span)", 0.0) + max(left, 0) * 1e-9
    n = max(len(trace["devices"]), 1)
    return {name: s / n for name, s in sums.items()}


def breakdown(trace, top=10):
    """The contract's `breakdown`: the device ops that took most time and
    the idle gaps by host span, longest first, at most `top` each."""
    def ranked(sums):
        return [[name, s] for name, s in sorted(
            sums.items(), key=lambda kv: -kv[1])[:top] if s > 0]

    return {"device_ops": ranked(seconds_by_name(trace)),
            "idle_gaps": ranked(idle_gaps_by_span(trace))}
