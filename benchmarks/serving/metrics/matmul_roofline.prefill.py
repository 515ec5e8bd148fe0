"""Kernels: the projections of the prefill chunks in the traced steps
against their roofline at the chunk's valid prompt rows (the head at
one row for a prompt's last chunk, at none before it), in %.  In a step
that also decodes, the decode program's operations are told apart by
their program: the one that ran the projections of decode-only steps."""
import trace_reduce
import work

NAME = "matmul_roofline.prefill"
UNIT = "%"
LAYER = "kernels (kernels/ops.py)"
MOVES = "ttft_p90_ms"
SOURCE = "device_trace"


def compute(record):
    tr = record["trace"]
    if not tr:
        return None
    weights = work.weight_map(record["config"])
    head = work.head_shape(record["config"])
    decode = {op.module
              for k, ops in tr["ops"].items() if tr["kind"][k] == "decode"
              for op in ops if trace_reduce.projection(op, weights)}
    mixed = "chunk+decode" in tr["kind"].values()
    if None in decode or (mixed and not decode):
        return None                     # the decode program is not known

    def rows(step, kn):
        if step["chunks"] is None:
            return None
        if kn == head:
            return sum(1 for _, _, final in step["chunks"] if final)
        return sum(valid for _, valid, _ in step["chunks"])

    return trace_reduce.roofline_share(
        record, ("chunk", "chunk+decode"), rows,
        lambda ops: [op for op in ops
                     if op.module not in decode])
