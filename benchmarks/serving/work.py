"""The work a step needs, from the model's shapes at the rows it serves.

Counts are of what the model needs, not of what a kernel executes: a
decode step counts its live rows (not the batch, not a kernel's padded
rows), a prefill chunk its valid prompt rows, and the head only the rows
whose next token is wanted.  So a change that stops padding, or skips
work nobody reads, shows as a higher share and never as less work.

bfloat16 weights and activations (2 bytes); FLOPs are 2 M K N per
matrix product.  What a step of a configuration multiplies, and what
its attention reads, is its family's (``families/<family>.py``): the
functions below that take a configuration ask it.
"""
from __future__ import annotations

import spec

BYTES = 2


def matmul(M: int, K: int, N: int) -> tuple[float, float]:
    """(FLOPs, bytes) of an (M, K) x (K, N) product; nothing where no row
    is needed."""
    if M <= 0:
        return 0.0, 0.0
    return 2.0 * M * K * N, float(BYTES * (K * N + M * K + M * N))


def matmuls(c: dict, rows: int, head_rows: int) -> list[tuple[float, float]]:
    """(FLOPs, bytes) of every projection call of one step over ``rows``
    token rows, the head over ``head_rows``: one entry per call."""
    return spec.family(c).matmuls(c, rows, head_rows)


def head_shape(c: dict) -> tuple[int, int]:
    """The head's (K, N) at the published vocabulary."""
    return spec.family(c).head_shape(c)


def weight_map(c: dict) -> dict[tuple[int, int], tuple[int, int]]:
    """Each weight shape the program may hold -> the model's (K, N) of
    the product it computes."""
    return spec.family(c).weight_map(c)


def roofline_s(calls, peaks: dict) -> float:
    """Least time of kernels run one after another: each bound by the
    larger of its FLOPs over peak FLOP/s and its bytes over peak B/s."""
    return sum(max(f / peaks["flops_bf16"], b / peaks["hbm_bytes_s"])
               for f, b in calls)


def decode_step(c: dict, rows: int, ctx: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step: ``rows`` live sequences whose
    visible context lengths sum to ``ctx``."""
    return spec.family(c).decode_step(c, rows, ctx)


def prefill_chunk(c: dict, start: int, valid: int,
                  final: bool) -> tuple[float, float]:
    """(FLOPs, bytes) of one prefill chunk: prompt rows ``start`` ..
    ``start + valid - 1`` of one request, each attending causally to the
    rows before it; the head only for the last row of the prompt."""
    return spec.family(c).prefill_chunk(c, start, valid, final)


def step_roofline_s(flops: float, byts: float, peaks: dict) -> float:
    """Least time of a whole step: the larger of its two bounds."""
    return max(flops / peaks["flops_bf16"], byts / peaks["hbm_bytes_s"])
