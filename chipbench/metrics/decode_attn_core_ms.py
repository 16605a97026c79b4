"""Device time of the decode program in the ``attn_core`` block: the
attention and its cache writes, in ms per traced step
(``blocks.read_ms``)."""
from chipbench import blocks


def read(r):
    return blocks.read_ms(r, "decode_step", ("attn_core",))
