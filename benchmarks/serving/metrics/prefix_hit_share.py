"""Engine: the share of prompt blocks that admission found already
cached, in %: the sum of each request's ``shared_blocks`` stamp (the
prompt's blocks the engine found in its prefix registry when it admitted
the request) over the sum of its full prompt blocks, over the requests
admitted in the window.  None where no request carries the stamp (a
program that stamps nothing)."""
NAME = "prefix_hit_share"
UNIT = "%"
LAYER = "engine (serve/paged.py)"
MOVES = "itl_p95_ms"
SOURCE = "program_counter"


def compute(record):
    run = record["serve"]
    bt = record["config"]["engine"]["block_tokens"]
    shared = full = 0
    stamped = False
    for tr in run["tracks"]:
        n = getattr(tr.req, "shared_blocks", None)
        t = getattr(tr.req, "t_admit", None)
        if n is None or t is None or not run["t0"] <= t < run["t_end"]:
            continue
        stamped = True
        shared += n
        full += tr.plen // bt
    return 100.0 * shared / full if stamped and full else None
