"""MoE: the share of the decode-only steps' device time spent in the
router, the routed experts and the shared experts, in %: the operations
whose weight operand has the shape of one of the MoE sublayer's weights
(the family's ``moe_weight_map``; found as ``trace_reduce.projection``
finds a projection, whichever code computes it), over the union of the
steps' device operations.  None for a family without MoE weights."""
import spec
import trace_reduce

NAME = "moe_share.decode"
UNIT = "%"
LAYER = "MoE (models/layers.py)"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def compute(record):
    tr = record["trace"]
    moe = getattr(spec.family(record["config"]), "moe_weight_map", None)
    if not tr or moe is None:
        return None
    shapes = moe(record["config"])
    took = busy = 0.0
    for k, ops in tr["ops"].items():
        if tr["kind"][k] != "decode":
            continue
        busy += trace_reduce.union_ns((o.start, o.end) for o in ops)
        took += sum(o.dur for o in ops
                    if trace_reduce.projection(o, shapes))
    return 100.0 * took / busy if took and busy else None
