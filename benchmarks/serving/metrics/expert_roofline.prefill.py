"""Kernels: the routed experts' grouped matmuls in the prefill chunks
against their roofline, in %.  Each grouped matmul (an operation of the
prefill program whose weight operand is an expert's gate, up or down
projection) needs the larger of its FLOPs at the chunk's routed rows
(valid rows x experts per token) over peak FLOP/s and, over peak bytes/s,
the bytes of the experts its rows chose and of its rows in and out.  The
experts chosen come from the engine's ``chunk_experts`` stamp, the
count per MoE layer of a chunk dispatched inside the step, taken as its
mean over the layers; a step with no such stamp counts every expert
(rows of one prompt route alike: 256 rows chose as few as 55 of 64 on a
TPU v5e), which reads high by up to that ratio.  Only traced steps
whose one chunk holds 256 valid rows or more count."""
import numpy as np

import trace_reduce
import work

NAME = "expert_roofline.prefill"
UNIT = "%"
LAYER = "kernels (kernels/ops.py)"
MOVES = "output_tok_s"
SOURCE = "device_trace"
#: the fewest valid rows of a chunk whose steps count
MIN_ROWS = 256
PROGRAM = "jit_prefill_chunk"


def compute(record):
    tr, peaks, c = record["trace"], record["peaks"], record["config"]
    keys = ("hidden_size", "moe_intermediate_size", "num_experts_per_tok",
            "n_routed_experts")
    if not tr or peaks is None or any(k not in c for k in keys):
        return None
    d, f, k, E = (c[x] for x in keys)
    experts = {(d, f): (d, f), (f, d): (f, d)}
    steps = {s["k"]: s for s in record["serve"]["steps"]}
    stamps = [(t, n) for track in record["serve"].get("tracks", ())
              for t, n in getattr(track.req, "chunk_experts", ())]
    need = took = 0.0
    for i, ops in tr["ops"].items():
        step = steps[i]
        chunks = step["chunks"]
        if tr["kind"][i] not in ("chunk", "chunk+decode") or not chunks \
                or len(chunks) != 1 or chunks[0][1] < MIN_ROWS:
            continue
        rows = chunks[0][1] * k
        chose = [n for t, n in stamps if step["t0"] <= t <= step["t1"]] \
            if stamps else []
        held = float(np.mean(np.asarray(chose[0]))) if len(chose) == 1 \
            else E
        for op in ops:
            kn = trace_reduce.projection(op, experts)
            if kn is None or not (op.module or "").startswith(PROGRAM):
                continue
            K, N = kn
            flops = 2.0 * rows * K * N
            byts = work.BYTES * (held * K * N + rows * (K + N))
            need += work.roofline_s([(flops, byts)], peaks)
            took += op.dur * 1e-9
    return 100.0 * need / took if took else None
