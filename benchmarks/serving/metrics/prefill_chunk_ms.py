"""Model step: device time of one prefill chunk: the device time of each
traced step that ran a chunk, less the mean decode-only step where it
also decoded, averaged over those steps."""
import trace_reduce

NAME = "prefill_chunk_ms"
UNIT = "ms"
LAYER = "model step (models/lm.py)"
MOVES = "ttft_p90_ms"
SOURCE = "device_trace"


def compute(record):
    busy = trace_reduce.busy_by_kind(record["trace"])
    dec = busy.get("decode")
    alone = busy.get("chunk", [])
    both = busy.get("chunk+decode", [])
    if both and not dec:
        return None
    less = sum(dec) / len(dec) if dec else 0.0
    parts = alone + [b - less for b in both]
    return 1e3 * sum(parts) / len(parts) if parts else None
