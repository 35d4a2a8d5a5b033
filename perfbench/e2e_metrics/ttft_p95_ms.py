"""Per chat, from the instant the open-loop schedule said to send it to
its first token event at the client; 95th percentile over the chats due
inside the window that were answered. A failed chat has no latency (it
is in ``failed``)."""

from ..stats import percentile

UNIT, BETTER, SOURCE = "ms", "lower", "host_clock"


def read(r):
    vals = [(c["first"] - c["due"]) * 1e3 for c in r.answered()]
    return percentile(vals, 95.0) if vals else None
