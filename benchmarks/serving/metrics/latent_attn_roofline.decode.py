"""Kernels: latent attention in the decode-only steps against its
roofline, in %.  Its operations are those with an operand of the latent
pool's rows (rank 3, ``kv_lora_rank + qk_rope_head_dim`` wide: one
layer's pool, or the rows gathered from it) whose output is not a whole
layer's pool (``n_blocks + 1`` blocks of ``block_tokens`` rows): the
gather and the absorbed scores and weighted sum.  An operation that
outputs the whole pool is the layer loop's plumbing (its per-layer
slice, copies and write-back of the pool, which donating the pool would
remove) or the write of the step's new rows into it, whose 576 values a
row and layer are nothing beside the context read; by shape the two
cannot be told apart, so both are left out.  A step needs the larger of
its bytes (every live row's visible context, ``decode_ctx`` tokens, once
in each layer) over peak bytes/s and its FLOPs (scores over the whole
row, the weighted sum over the latent, per head) over peak FLOP/s."""
import trace_reduce
import work

NAME = "latent_attn_roofline.decode"
UNIT = "%"
LAYER = "kernels (kernels/ops.py)"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def _latent(op, width: int, pool: tuple[int, int, int]) -> bool:
    sig = trace_reduce.signature(op)
    return sig is not None and sig[0] not in trace_reduce.CONTAINERS \
        and any(len(s) == 3 and s[-1] == width for s in sig[2]) \
        and not any(tuple(s[-3:]) == pool for s in sig[1])


def compute(record):
    tr, peaks, c = record["trace"], record["peaks"], record["config"]
    keys = ("kv_lora_rank", "qk_rope_head_dim", "num_attention_heads",
            "num_hidden_layers")
    if not tr or peaks is None or any(k not in c for k in keys):
        return None
    r, rope, H, L = (c[x] for x in keys)
    width = r + rope
    e = c["engine"]
    pool = (e["pool_tokens"] // e["block_tokens"] + 1, e["block_tokens"],
            width)
    steps = {s["k"]: s for s in record["serve"]["steps"]}
    need = took = 0.0
    for k, ops in tr["ops"].items():
        if tr["kind"][k] != "decode":
            continue
        mine = [op for op in ops if _latent(op, width, pool)]
        if not mine:
            continue
        ctx = steps[k]["decode_ctx"]
        flops = 2.0 * H * (2 * r + rope) * ctx * L
        byts = float(work.BYTES * width * ctx * L)
        need += work.step_roofline_s(flops, byts, peaks)
        took += sum(op.dur for op in mine) * 1e-9
    return 100.0 * need / took if took else None
