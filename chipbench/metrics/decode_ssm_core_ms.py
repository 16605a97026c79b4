"""Device time of the decode program in the ``ssm_core`` block: each
Mamba2 layer's convolution, state update, D skip and grouped gated norm,
in ms per traced step (``blocks.read_ms``)."""
from chipbench import blocks


def read(r):
    return blocks.read_ms(r, "decode_step", ("ssm_core",))
