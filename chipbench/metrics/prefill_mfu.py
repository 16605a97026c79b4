"""The prefill program's share of the chip's bf16 peak, in %: the
operations a prefill needs (``prefill_flops`` of the cell's
architecture, ``reference/<name>.py``) times the
prefills of the traced segment, over the device time of those prefills
(``tracing.reduce``: the device operations inside the host's
``prefill`` annotations) times the peak.  The prompt's upload and the
first token's sampling are not in it."""


def read(r):
    t = r.trace
    if not t or not r.traced_prefills:
        return None
    s = t["steps"]["prefill"]
    if s["n"] != r.traced_prefills or s["busy_s"] <= 0:
        return None
    flops = r.prefill_flops() * r.traced_prefills
    return 100.0 * flops / (s["busy_s"] * r.peaks["bf16_flops"])
