"""The engine's stamps on each request, read by the engine's per-layer
metrics: ``t_submit`` (``submit()`` took it), ``t_admit`` (it left the
queue for a slot), ``t_prefill_start`` (its first prefill dispatched) and
``t_first`` (its first token read back), all on the harness's clock.
Between them they split the time to the first token: the wait for a
slot and blocks, the wait for the request's turn at the one-chunk-per-
step prefill, and its own prefill."""
import numpy as np


def p90_ms(record, since: str, until: str):
    """90th percentile of ``until - since`` in ms, over the requests due
    in the window that carry both stamps; None where none does (a program
    that stamps nothing)."""
    run = record["serve"]
    spans = []
    for tr in run["tracks"]:
        a = getattr(tr.req, since, None)
        b = getattr(tr.req, until, None)
        if a is not None and b is not None \
                and run["t0"] <= tr.arrival < run["t_end"]:
            spans.append(b - a)
    return 1e3 * float(np.percentile(spans, 90)) if spans else None
