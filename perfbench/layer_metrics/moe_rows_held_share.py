"""The share of the routers' assignments that landed on experts this
chip holds: ``expert_rows_held`` over ``expert_rows_held`` +
``expert_rows_elsewhere`` (rows x experts a token x routed blocks x
steps), both as the program counted them on the device inside each
decode step, over the ``decode_window`` spans of the traced window. A
chip that holds one of two ranks' experts reads 0.5 when the router is
even across the ranks; what is over is work this chip does for want of
balance, what is under is work it is spared. A program whose spans carry
no such attrs (every expert held, or no routing counted) gives nothing
to read."""

LAYER, UNIT, BETTER = "runner and model", "ratio", "lower"
SOURCE, MOVES = "program_span", "out_tokens_per_s_per_chip"


def read(r):
    spans = [
        s for s in r.spans_in_trace("decode_window")
        if "expert_rows_held" in s[3] and "expert_rows_elsewhere" in s[3]
    ]
    held = sum(float(s[3]["expert_rows_held"]) for s in spans)
    total = held + sum(float(s[3]["expert_rows_elsewhere"]) for s in spans)
    return None if total <= 0 else held / total
