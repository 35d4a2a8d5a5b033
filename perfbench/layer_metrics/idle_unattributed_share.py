"""Of the device's idle time in the traced window, the share under no
flight-recorder span: the reduced trace's ``gaps_ns`` (first chip),
each gap split by OVERLAP across the spans under it, what overlaps none
over all gap seconds. The offset between the trace's clock and
``time.monotonic()`` is that of the harness's first sync mark:
``trace_span[0] * 1e9 - trace["window_ns"][0]``."""

import bisect

LAYER, UNIT, BETTER = "device", "%", "lower"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"


def gaps_mono_s(r):
    """The gaps as (start, end) seconds on the monotonic clock."""
    if r.trace is None or r.trace_span is None:
        return []
    offset_ns = r.trace_span[0] * 1e9 - r.trace["window_ns"][0]
    return [
        ((s + offset_ns) * 1e-9, (e + offset_ns) * 1e-9)
        for s, e in r.trace.get("gaps_ns") or ()
    ]


def split_by_phase(r):
    """{span name: idle seconds under it}, plus ``unattributed``. A gap
    under plan -> dispatch -> accept -> emit gives each its own part;
    where spans of two threads overlap, the one that started first
    takes the part they share."""
    spans = sorted((s[1], s[2], s[0]) for s in r.spans if s[2] > s[1])
    starts = [a for a, _b, _n in spans]
    reach, top = [], float("-inf")   # latest end among spans[: i + 1]
    for _a, b, _n in spans:
        top = max(top, b)
        reach.append(top)
    out = {"unattributed": 0.0}
    for lo, hi in gaps_mono_s(r):
        cursor = lo
        first = bisect.bisect_right(reach, lo)
        last = bisect.bisect_left(starts, hi)
        for a, b, name in spans[first:last]:
            a, b = max(a, cursor), min(b, hi)
            if b > a:
                out["unattributed"] += a - cursor
                out[name] = out.get(name, 0.0) + (b - a)
                cursor = b
        out["unattributed"] += hi - cursor
    return out


def read(r):
    gaps = gaps_mono_s(r)
    total = sum(e - s for s, e in gaps)
    if total <= 0:
        return None
    return 100.0 * split_by_phase(r)["unattributed"] / total
