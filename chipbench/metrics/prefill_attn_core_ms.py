"""Device time of the prefill program in the ``attn_core`` block: the
attention and its cache writes, in ms per traced prefill
(``blocks.read_ms``)."""
from chipbench import blocks


def read(r):
    return blocks.read_ms(r, "prefill", ("attn_core",))
