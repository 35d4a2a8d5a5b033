"""Per chat, (last token event - first) / (completion tokens - 1), so a
fused window that delivers eight tokens at once does not read as a
burst; 95th percentile over answered chats with two tokens or more."""

from ..stats import percentile

UNIT, BETTER, SOURCE = "ms", "lower", "host_clock"


def read(r):
    vals = [
        (c["last"] - c["first"]) * 1e3 / (c["tokens_seen"] - 1)
        for c in r.answered() if c["tokens_seen"] >= 2
    ]
    return percentile(vals, 95.0) if vals else None
