"""Seeded filler text of an exact length in characters (ASCII, so a
byte tokenizer sees one token a character)."""

from __future__ import annotations

WORDS = (
    "battery life is incredible and it charges fast stopped working after "
    "two weeks very disappointed decent value for the price but build feels "
    "cheap exactly as described shipping was quick screen scratches way too "
    "easily customer support resolved my issue in minutes loud under load "
    "returned it kids love survived several drops already order arrived "
    "late box damaged works fine so far would buy again colour differs from "
    "photo manual unclear setup took an hour firmware update fixed pairing"
).split()


def text_of_length(rng, n_chars: int, head: str = "") -> str:
    """``head`` then random words, cut to exactly ``n_chars``."""
    parts = [head] if head else []
    size = len(head)
    while size < n_chars:
        w = WORDS[int(rng.integers(0, len(WORDS)))]
        parts.append(w)
        size += len(w) + 1
    return " ".join(parts)[:n_chars].ljust(n_chars, ".")
