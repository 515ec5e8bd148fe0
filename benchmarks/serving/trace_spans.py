"""What the program itself writes into the profiler's trace, read back:
each device operation's ``op_name`` (and so the sublayer scope it ran
in), and the engine's ``serve.*`` spans.

``jax.profiler.ProfileData`` gives a device operation's name (its HLO
text) but not the event metadata's stats, where the ``tf_op`` stat holds
the ``op_name`` the program's ``jax.named_scope``s wrote, e.g.
``jit(decode_step_paged)/while/body/attn.core/dot_general``.  This
module reads those from the ``.xplane.pb`` bytes with a plain protobuf
walker (field numbers of ``tsl/profiler/protobuf/xplane.proto``) and
joins them to ``trace_reduce``'s operations by event name.

The scopes are those of ``models/lm.py`` and ``models/layers.py``
(:data:`SCOPES`).  An operation inside the layer scan (``while/body``)
and in no scope is the layer loop's own: per-layer slices of the stacked
weights and pool, the pool written back.  An operation with no
``op_name`` (XLA makes some, such as the float32 casts of the gathered
keys and values, without metadata) takes the scope of the next leaf
operation on the device's timeline that has one: such an operation feeds
the one after it.

The engine's spans are host events whose names start with ``serve.``,
each with its arguments (``rows``, ``valid``, ...) and its parent, the
innermost ``serve.*`` span on the same thread that holds it.  Host and
device events share the trace's clock (``trace_reduce`` relies on it).

No metric reads these yet: a run deletes its trace once
``trace_reduce.reduce`` has read it, so a reading of scopes or spans
waits for the harness to pass them on.  The tests check them on the
chip recordings in ``testdata/`` and ``testdata_spans/``.
"""
from __future__ import annotations

import dataclasses
import gzip

#: the sublayer scopes the model step writes, in the order a layer runs
SCOPES = ("embed", "attn.qkv", "attn.kv_write", "attn.core", "attn.out",
          "mlp", "head")
#: inside the layer scan, in no sublayer scope
LOOP = "layer loop"
#: outside the layer scan and every scope
OTHER = "other"
SPAN_PREFIX = "serve."

# xplane.proto field numbers
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 4, 5
_MAP_VALUE = 2           # a map entry's value
_MD_ID, _MD_NAME, _EVENT_MD_STATS = 1, 2, 5
_STAT_MD_ID, _STAT_STR, _STAT_REF = 1, 5, 7
TF_OP = "tf_op"


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one protobuf message:
    an int for a varint, a memoryview for the rest."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, val


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def load(path: str) -> bytes:
    """The serialized XSpace at ``path`` (``.xplane.pb`` or gzipped)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return f.read()


def op_names(data: bytes) -> dict[str, dict[str, str]]:
    """For each device plane, each operation's event name -> the
    ``op_name`` of its ``tf_op`` stat (the trailing ``:`` dropped)."""
    out = {}
    for field, plane in _fields(memoryview(data)):
        if field != _SPACE_PLANES:
            continue
        name, events, stats = None, [], {}
        for f, v in _fields(plane):
            if f == _PLANE_NAME:
                name = _text(v)
            elif f == _PLANE_EVENT_MD:
                events.append(v)
            elif f == _PLANE_STAT_MD:
                md = dict(_fields(dict(_fields(v)).get(_MAP_VALUE, b"")))
                stats[md.get(_MD_ID, 0)] = _text(md.get(_MD_NAME, b""))
        if not name or not name.startswith("/device:") or "CPU" in name:
            continue
        tf_op = [k for k, s in stats.items() if s == TF_OP]
        if not tf_op:
            continue
        names = {}
        for entry in events:
            ev = dict(_fields(entry)).get(_MAP_VALUE, b"")
            ev_name, op = None, None
            for f, v in _fields(ev):
                if f == _MD_NAME:
                    ev_name = _text(v)
                elif f == _EVENT_MD_STATS:
                    st = dict(_fields(v))
                    if st.get(_STAT_MD_ID) != tf_op[0]:
                        continue
                    if _STAT_STR in st:
                        op = _text(st[_STAT_STR])
                    elif _STAT_REF in st:
                        op = stats.get(st[_STAT_REF])
            if ev_name and op:
                names[ev_name] = op.rstrip(":")
        out[name] = names
    return out


@dataclasses.dataclass
class Span:
    """One engine span on the trace's clock (ns)."""
    name: str
    start: float
    end: float
    args: dict
    parent: int | None = None   # index of the span that holds it


def engine_spans(data: bytes) -> list[Span]:
    """The ``serve.*`` host events, by start, each with its arguments and
    the index of its parent."""
    from jax.profiler import ProfileData
    prof = ProfileData.from_serialized_xspace(data)
    found = [(float(ev.start_ns), -float(ev.duration_ns), (i, j), ev)
             for i, plane in enumerate(prof.planes)
             if plane.name.startswith("/host:")
             for j, line in enumerate(plane.lines)
             for ev in line.events if ev.name.startswith(SPAN_PREFIX)]
    found.sort(key=lambda x: x[:3])
    out: list[Span] = []
    open_on: dict[tuple, list[int]] = {}     # per thread, the spans open
    for start, neg_dur, line, ev in found:
        end = start - neg_dur
        stack = open_on.setdefault(line, [])
        while stack and out[stack[-1]].end < end:
            stack.pop()
        out.append(Span(ev.name, start, end, dict(ev.stats),
                        stack[-1] if stack else None))
        stack.append(len(out) - 1)
    return out


def scope_of(op_name: str | None) -> str | None:
    """The sublayer scope an ``op_name`` names; :data:`LOOP` inside the
    layer scan in no scope, :data:`OTHER` elsewhere, None for no name."""
    if not op_name:
        return None
    parts = op_name.split("/")
    for p in parts:
        if p in SCOPES:
            return p
    return LOOP if "while" in parts and "body" in parts else OTHER


def scopes(leaves: list, names: dict[str, str]) -> list[str | None]:
    """The scope of each of ``leaves`` (in device-timeline order); an op
    with no op_name takes the scope of the next op that has one (None
    where no later op has one)."""
    out = [scope_of(names.get(op.name)) for op in leaves]
    nxt = None
    for i in range(len(out) - 1, -1, -1):
        if out[i] is None:
            out[i] = nxt
        else:
            nxt = out[i]
    return out


def read(path: str) -> dict:
    """The op_names of the trace's first device and its engine spans."""
    data = load(path)
    names = op_names(data)
    return {"names": next(iter(names.values()), {}),
            "spans": engine_spans(data)}
