"""Median client TTFT minus the median of the gateway's own ``ttft_s``
for the same chats (the trace store's ``finish`` events, matched by the
trace id the client sent; the store keeps the newest 256). What is left
is the entry's share: lateness of the send, parsing, tokenizing the
prompt, the prefix probe, and waking the consumer."""

from ..stats import median

LAYER, UNIT, BETTER = "entry", "ms", "lower"
SOURCE, MOVES = "program_span", "ttft_p95_ms"


def read(r):
    client, gateway = [], []
    for c in r.answered():
        g = r.sut.gateway_ttft_s(c["trace_id"])
        if g is not None:
            client.append((c["first"] - c["due"]) * 1e3)
            gateway.append(float(g) * 1e3)
    if len(client) < 5:
        return None
    return median(client) - median(gateway)
