"""The decode program's ``ssm_core`` block against its roofline, in %:
the least time in which one step can read and write the Mamba2
convolution and ssm states of every layer (``ssm_core_bytes`` of the
cell's architecture, ``reference/<name>.py``, at the chip's HBM
bandwidth) over the block's measured ms per traced step
(``decode_ssm_core_ms``).  None where the architecture has no such
states or the trace names no ``ssm_core``."""
from chipbench import blocks


def read(r):
    state_bytes = getattr(r.arch, "ssm_core_bytes", None)
    ms = blocks.read_ms(r, "decode_step", ("ssm_core",))
    if state_bytes is None or not ms:
        return None
    least_ms = 1e3 * state_bytes(r.dims, r.batch) \
        / r.peaks["hbm_bytes_per_s"]
    return 100.0 * least_ms / ms
