"""How close a forward of a model that generates by blocks comes to the
HBM bound: the bytes a forward must move
(``bytes_and_flops_bd.forward_bytes``: the layers' weights once, of the
experts those ``experts_touched``, each row's cached K/V once for the
whole block at the spans' ``batch`` and ``avg_ctx``; the head and the
block's logits in a DENOISING forward, the block's K/V write in a COMMIT
forward), weighted by the forwards of each kind the ``decode_window``
spans report (``denoise_forwards``, ``commit_forwards``), over the peak
bytes/s, as a share of the measured ``decode_step_device_ms`` (whose
``steps`` are forwards in such a cell). The whole step's share: the
block kernel's, the grouped product's and the sampler's time are all in
its denominator.

Where the configuration has no ``block_length`` or the spans carry no
``denoise_forwards`` (a program that generates a token a forward) there
is nothing to read."""

from .. import bytes_and_flops_bd as counts
from .decode_step_device_ms import steps_and_seconds

LAYER, UNIT, BETTER = "kernels", "%", "higher"
SOURCE, MOVES = "device_trace", "out_tokens_per_s_per_chip"


def read(r):
    if "block_length" not in r.cfg:
        return None
    got = steps_and_seconds(r)
    spans = [s for s in r.spans_in_trace("decode_window")
             if "denoise_forwards" in s[3] and "experts_touched" in s[3]]
    if got is None or not spans:
        return None
    step_s = got[0] / got[1]
    width = 2 if "16" in str(r.cfg["engine"].get("param_dtype")) else 4
    total = forwards = 0.0
    for s in spans:
        a = s[3]
        for kind in ("denoise", "commit"):
            n = float(a.get(kind + "_forwards", 0))
            total += n * counts.forward_bytes(
                r.cfg, kind=kind, batch=float(a.get("batch", 0)),
                ctx=float(a.get("avg_ctx", 0)),
                experts_touched=float(a["experts_touched"]),
                weight_dtype_bytes=width, kv_dtype_bytes=width,
            )
            forwards += n
    if forwards <= 0:
        return None
    least_s = total / forwards / r.n_chips / r.peaks()["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
