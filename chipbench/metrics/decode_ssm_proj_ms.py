"""Device time of the decode program in the ``ssm_proj`` block: each
Mamba2 layer's input and output projections, in ms per traced step
(``blocks.read_ms``)."""
from chipbench import blocks


def read(r):
    return blocks.read_ms(r, "decode_step", ("ssm_proj",))
