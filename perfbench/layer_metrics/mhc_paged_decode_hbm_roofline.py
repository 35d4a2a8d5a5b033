"""How close the paged decode kernel's latent variant comes to the HBM
bound under a residual stream of several lanes: what
``mla_paged_decode_hbm_roofline`` reads (the latent rows the decode
steps' rows needed, ``kv_lora_rank + qk_rope_head_dim`` = 576 values a
token a layer read once, ``bytes_and_flops_mla.latent_bytes_per_token``,
over the peak bytes/s, as a share of the ``paged_decode_attention`` ops'
device time in the traced window), for a configuration with
``hc_mult``: that reader is held to its own cell, and the kernel is a
third of this cell's mixer time in a decode step, which the whole
step's share cannot tell from the experts' reads.

Where the configuration has no ``hc_mult`` or the other reader finds
nothing there is nothing to read."""

from . import mla_paged_decode_hbm_roofline as latent

LAYER, UNIT, BETTER = latent.LAYER, latent.UNIT, latent.BETTER
SOURCE, MOVES = latent.SOURCE, latent.MOVES


def read(r):
    if "hc_mult" not in r.cfg:
        return None
    return latent.read(r)
