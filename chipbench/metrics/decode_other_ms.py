"""Device time of the decode program in ``other``: the operations that
no block names, in ms per traced step (``blocks.read_ms``)."""
from chipbench import blocks


def read(r):
    return blocks.read_ms(r, "decode_step", (blocks.OTHER,))
