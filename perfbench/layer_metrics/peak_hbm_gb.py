"""``memory_stats()["peak_bytes_in_use"]`` on the fullest chip of the
mesh, in GB (1e9)."""

LAYER, UNIT, BETTER = "device", "GB", "lower"
SOURCE, MOVES = "program_counter", "out_tokens_per_s_per_chip"


def read(r):
    return r.memory_peak_bytes / 1e9 if r.memory_peak_bytes else None
