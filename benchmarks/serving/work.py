"""The work a step needs, from the model's shapes at the rows it serves.

Counts are of what the model needs, not of what a kernel executes: a
decode step counts its live rows (not the batch, not a kernel's padded
rows), a prefill chunk its valid prompt rows, and the head only the rows
whose next token is wanted.  So a change that stops padding, or skips
work nobody reads, shows as a higher share and never as less work.

bfloat16 weights and activations (2 bytes); FLOPs are 2 M K N per
matrix product.  Attention's FLOPs are 4 hd per head, query and visible
key (scores and the weighted sum); its bytes are the keys and values it
reads and writes.
"""
from __future__ import annotations

BYTES = 2


def dims(c: dict) -> dict:
    d, H = c["hidden_size"], c["num_attention_heads"]
    hd = d // H
    return {"d": d, "F": c["intermediate_size"], "H": H, "hd": hd,
            "Hkv": c["num_key_value_heads"], "L": c["num_hidden_layers"],
            "V": c["vocab_size"]}


def layer_mats(c: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of one layer's projections."""
    m = dims(c)
    d, F, q, kv = m["d"], m["F"], m["H"] * m["hd"], m["Hkv"] * m["hd"]
    return [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d),
            ("w_gate", d, F), ("w_up", d, F), ("w_down", F, d)]


def head_shape(c: dict) -> tuple[int, int]:
    m = dims(c)
    return m["d"], m["V"]


def weight_map(c: dict) -> dict[tuple[int, int], tuple[int, int]]:
    """Each weight shape the program may hold -> the model's (K, N): the
    head's vocabulary padded to 256 rows maps to the published one."""
    m = dims(c)
    vp = -(-m["V"] // 256) * 256
    out = {(k, n): (k, n) for _, k, n in layer_mats(c)}
    out[(m["d"], m["V"])] = out[(m["d"], vp)] = (m["d"], m["V"])
    return out


def kv_token_bytes(c: dict) -> int:
    m = dims(c)
    return 2 * m["Hkv"] * m["hd"] * BYTES * m["L"]


def matmul(M: int, K: int, N: int) -> tuple[float, float]:
    """(FLOPs, bytes) of an (M, K) x (K, N) product; nothing where no row
    is needed."""
    if M <= 0:
        return 0.0, 0.0
    return 2.0 * M * K * N, float(BYTES * (K * N + M * K + M * N))


def matmuls(c: dict, rows: int, head_rows: int) -> list[tuple[float, float]]:
    """(FLOPs, bytes) of every projection call of one step over ``rows``
    token rows, the head over ``head_rows``: one entry per call."""
    m = dims(c)
    out = []
    if rows:
        per_layer = [matmul(rows, K, N) for _, K, N in layer_mats(c)]
        out += per_layer * m["L"]
    if head_rows:
        out.append(matmul(head_rows, m["d"], m["V"]))
    return out


def roofline_s(calls, peaks: dict) -> float:
    """Least time of kernels run one after another: each bound by the
    larger of its FLOPs over peak FLOP/s and its bytes over peak B/s."""
    return sum(max(f / peaks["flops_bf16"], b / peaks["hbm_bytes_s"])
               for f, b in calls)


def decode_step(c: dict, rows: int, ctx: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step: ``rows`` live sequences whose
    visible context lengths sum to ``ctx``.  Bytes count every weight once
    (the step reads them all whatever its batch), the live keys and values
    read, the new ones written and the projections' activations."""
    m = dims(c)
    calls = matmuls(c, rows, rows)
    flops = sum(f for f, _ in calls) + 4.0 * m["H"] * m["hd"] * ctx * m["L"]
    byts = sum(b for _, b in calls) + kv_token_bytes(c) * (ctx + rows) \
        + rows * m["d"] * BYTES
    return flops, byts


def prefill_chunk(c: dict, start: int, valid: int,
                  final: bool) -> tuple[float, float]:
    """(FLOPs, bytes) of one prefill chunk: prompt rows ``start`` ..
    ``start + valid - 1`` of one request, each attending causally to the
    rows before it; the head only for the last row of the prompt."""
    m = dims(c)
    calls = matmuls(c, valid, 1 if final else 0)
    visible = valid * start + valid * (valid + 1) // 2
    flops = sum(f for f, _ in calls) \
        + 4.0 * m["H"] * m["hd"] * visible * m["L"]
    byts = sum(b for _, b in calls) \
        + kv_token_bytes(c) * (start + valid) + valid * m["d"] * BYTES
    return flops, byts


def step_roofline_s(flops: float, byts: float, peaks: dict) -> float:
    """Least time of a whole step: the larger of its two bounds."""
    return max(flops / peaks["flops_bf16"], byts / peaks["hbm_bytes_s"])
