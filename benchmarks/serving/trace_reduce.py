"""From the profiler's trace of part of the window to what the per-layer
metrics read: the device's operations, the harness's step spans, and
which operations belong to which step.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``.  A device is a plane whose name starts with
``/device:`` and names no CPU; its operations are the events of its
``XLA Ops`` line.  The harness's spans are the host events named
``bench.step.<k>``, one around each ``engine.step()``.  Host and device
events share one clock in the file.  In a traced run each span closes
only when the step's device work is done, so an operation belongs to
the step whose span holds its start.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import os
import re

STEP = re.compile(r"^bench\.step\.(\d+)$")
#: an array type in HLO text: ``bf16[128,3072]{1,0:T(8,128)}``
SHAPE = re.compile(r"\b(?:bf16|f16|f32|f64|s8|s16|s32|s64|u8|u32|pred|"
                   r"f8e4m3fn|f8e5m2)\[([0-9,]*)\]")
#: an HLO instruction: ``%name = <output type> opcode(<operands>), ...``
INSTR = re.compile(r"=\s*(?P<out>.*?)\s+(?P<opcode>[a-z][a-z0-9-]*)\("
                   r"(?P<args>.*)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: opcodes whose event spans the events of the computations it calls
CONTAINERS = ("while", "conditional", "call", "async-start", "async-done")
#: the breakdown lists at most this many entries in each list
TOP = 10


@dataclasses.dataclass
class Op:
    """One operation that ran on the device."""
    name: str                   # the HLO instruction, as the trace names it
    start: float                # ns
    dur: float                  # ns
    module: str | None = None   # the compiled program that ran it

    @property
    def end(self) -> float:
        return self.start + self.dur


def xplane_path(directory: str) -> str | None:
    """The newest trace file under ``directory`` (``.xplane.pb``, or
    gzipped ``.xplane.pb.gz``)."""
    found = sorted(p for pat in ("*.xplane.pb", "*.xplane.pb.gz")
                   for p in glob.glob(os.path.join(directory, "**", pat),
                                      recursive=True))
    return found[-1] if found else None


def read(path: str) -> tuple[dict[str, list[Op]], list[tuple[int, float,
                                                             float]]]:
    """Device operations by plane, each with the program that ran it, and
    the step spans (k, start, end) in ns, from the trace file at
    ``path``."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices: dict[str, list[Op]] = {}
    spans = []
    for plane in data.planes:
        name = plane.name
        if name.startswith("/device:") and "CPU" not in name:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [Op(ev.name, float(ev.start_ns),
                               float(ev.duration_ns)) for ev in line.events]
                elif line.name == MODULES_LINE:
                    modules += [(float(ev.start_ns),
                                 float(ev.start_ns + ev.duration_ns), ev.name)
                                for ev in line.events]
            if ops:
                ops.sort(key=lambda o: o.start)
                _attach_modules(ops, sorted(modules))
                devices[name] = ops
        elif name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    m = STEP.match(ev.name)
                    if m:
                        spans.append((int(m.group(1)), float(ev.start_ns),
                                      float(ev.start_ns + ev.duration_ns)))
    spans.sort(key=lambda s: s[1])
    return devices, spans


def _attach_modules(ops: list[Op], modules) -> None:
    starts = [m[0] for m in modules]
    for op in ops:
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and op.start <= modules[i][1]:
            op.module = modules[i][2]


def leaves(ops: list[Op]) -> list[Op]:
    """The operations that hold no other: a ``while`` loop's event spans
    the events of its body, and only the body's count as work."""
    out = []
    for i, op in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or nxt.start >= op.end:
            out.append(op)
    return out


def short_name(op: Op) -> str:
    """``%matmul.83 = bf16[..] custom-call(..)`` -> ``matmul.83
    (custom-call)``: the instruction's name and opcode."""
    m = INSTR.search(op.name)
    head = op.name.split("=", 1)[0].strip().lstrip("%")
    return f"{head} ({m.group('opcode')})" if m else op.name[:80]


def _dims(text: str) -> list[tuple[int, ...]]:
    return [tuple(int(x) for x in m.group(1).split(",") if x)
            for m in SHAPE.finditer(text)]


def signature(op: Op):
    """(opcode, output shapes, operand shapes) of the op's HLO
    instruction (a TPU's ``XLA Ops`` events are named by it), or None
    where its name is no instruction text with operand types."""
    m = INSTR.search(op.name)
    if m is None or not SHAPE.search(op.name):
        return None
    args, depth, end = m.group("args"), 1, None
    for i, ch in enumerate(args):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            end = i
            break
    return m.group("opcode"), _dims(m.group("out")), _dims(args[:end])


def projection(op: Op, weights: dict[tuple[int, int], tuple[int, int]]):
    """The model's (K, N) of the projection that ``op`` computes, or None.
    ``weights`` maps each weight shape the program may hold (the head's
    with its vocabulary padded, too) to the model's (K, N).  An op
    computes a projection when it has one output, one operand is such a
    weight (its last two dims, so a stacked weight sliced inside the op
    counts), and the output is not that weight itself (a copy of a
    weight slice is no projection) but has the weight's N as its last
    dim.  A loop or call, whose operands are whole weight stacks, is
    none."""
    sig = signature(op)
    if sig is None or sig[0] in CONTAINERS or len(sig[1]) != 1:
        return None
    (out,), operands = sig[1], sig[2]
    for shp in operands:
        kn = tuple(shp[-2:])
        if len(shp) >= 2 and kn in weights and out[-1:] == kn[-1:] \
                and tuple(out[-2:]) != kn:
            return weights[kn]
    return None


def roofline_share(record: dict, kinds: tuple[str, ...], rows_of,
                   programs=None) -> float | None:
    """Projections' least time (``work.roofline_s`` at the rows the model
    needs) over their device time, in %, over the traced steps of the
    given kinds.  ``rows_of(step, (K, N))`` gives the rows a projection
    of that step needs (None: leave the step out); ``programs(step_ops)``
    keeps the ops of the program being measured.  None where no
    projection was found."""
    import work
    tr, peaks, c = record["trace"], record["peaks"], record["config"]
    if not tr or peaks is None:
        return None
    steps = {s["k"]: s for s in record["serve"]["steps"]}
    weights = work.weight_map(c)
    need = took = 0.0
    for k, ops in tr["ops"].items():
        if tr["kind"][k] not in kinds:
            continue
        for op in (programs(ops) if programs else ops):
            kn = projection(op, weights)
            rows = None if kn is None else rows_of(steps[k], kn)
            if rows is not None:
                need += work.roofline_s([work.matmul(rows, *kn)], peaks)
                took += op.dur * 1e-9
    return 100.0 * need / took if took else None


def union_ns(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def by_step(ops: list[Op], spans) -> dict[int, list[Op]]:
    """The operations whose start lies in each step's span."""
    out: dict[int, list[Op]] = {k: [] for k, _, _ in spans}
    j = 0
    for op in ops:
        while j < len(spans) and spans[j][2] < op.start:
            j += 1
        if j < len(spans) and spans[j][1] <= op.start <= spans[j][2]:
            out[spans[j][0]].append(op)
    return out


def busy_by_kind(trace: dict | None) -> dict[str, list[float]]:
    """Device seconds of each traced step, grouped by the step's kind
    (``decode``, ``chunk``, ``chunk+decode``, ``idle``)."""
    out: dict[str, list[float]] = {}
    if not trace:
        return out
    for k, ops in trace["ops"].items():
        busy = union_ns((o.start, o.end) for o in ops) * 1e-9
        out.setdefault(trace["kind"][k], []).append(busy)
    return out


def _gap_label(t: float, spans, starts, step_kind) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t <= spans[i][2]:
        return f"host inside engine.step ({step_kind[spans[i][0]]})"
    return "host between steps (harness, waiting for arrivals)"


def reduce(directory: str, run: dict) -> dict | None:
    """The trace under ``directory`` reduced for the metric readers, or
    None where it holds no device operation."""
    path = xplane_path(directory)
    if path is None:
        return None
    devices, spans = read(path)
    if not devices or not spans:
        return None
    steps = {s["k"]: s for s in run["steps"]}
    spans = [sp for sp in spans if sp[0] in steps]
    if not spans:
        return None
    w0, w1 = spans[0][1], spans[-1][2]
    busy, per_step = [], {}
    kind = {}
    for k, _, _ in spans:
        s = steps[k]
        kind[k] = ("chunk+decode" if s["d_chunks"] and s["d_decode"] else
                   "chunk" if s["d_chunks"] else
                   "decode" if s["d_decode"] else "idle")
    starts = [sp[1] for sp in spans]
    totals: dict[str, float] = {}
    gaps: dict[str, float] = {}
    for plane, ops in devices.items():
        ops = [o for o in ops if w0 <= o.start <= w1]
        busy.append(union_ns((o.start, min(o.end, w1)) for o in ops))
        for o in leaves(ops):
            n = short_name(o)
            totals[n] = totals.get(n, 0.0) + o.dur
        prev = w0
        for o in ops:
            if o.start > prev:
                lab = _gap_label((prev + o.start) / 2, spans, starts, kind)
                gaps[lab] = gaps.get(lab, 0.0) + (o.start - prev)
            prev = max(prev, o.end)
        if w1 > prev:
            lab = _gap_label((prev + w1) / 2, spans, starts, kind)
            gaps[lab] = gaps.get(lab, 0.0) + (w1 - prev)
        if not per_step:
            per_step = by_step(ops, spans)
    n_dev = len(devices)
    window_ns = w1 - w0
    return {
        "busy_s": sum(busy) / n_dev * 1e-9,
        "window_s": window_ns * 1e-9,
        "kind": kind,
        "ops": per_step,
        "breakdown": {
            "device_ops": [[n, t / n_dev * 1e-9] for n, t in sorted(
                totals.items(), key=lambda x: -x[1])[:TOP]],
            "idle_gaps": [[n, t / n_dev * 1e-9] for n, t in sorted(
                gaps.items(), key=lambda x: -x[1])[:TOP]],
        },
    }
