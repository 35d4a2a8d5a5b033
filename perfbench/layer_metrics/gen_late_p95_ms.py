"""How late the open-loop generator fired against its schedule, 95th
percentile: a starved generator must not read as a fast server."""

from ..stats import percentile

LAYER, UNIT, BETTER = "load generator", "ms", "lower"
SOURCE, MOVES = "host_clock", "ttft_p95_ms"


def read(r):
    vals = [
        (c["fired"] - c["due"]) * 1e3 for c in r.window_chats()
        if c["fired"] is not None
    ]
    return percentile(vals, 95.0) if vals else None
