"""The decode step program's share of its roofline, in %: the least
time the decode steps of the traced segment could take at the chip's
peaks (per step the larger of operations over peak FLOP/s and needed
bytes over peak bandwidth: ``decode_roofline_s`` of the cell's
architecture, ``reference/<name>.py``), summed, over the device time of
those steps (``tracing.reduce``: the device operations inside the
host's ``decode_step`` annotations).  The host's sampling and token
transfer between steps is not in it; the device's idle share reads
that."""


def read(r):
    t = r.trace
    if not t or not r.traced_positions:
        return None
    s = t["steps"]["decode_step"]
    if s["n"] != len(r.traced_positions) or s["busy_s"] <= 0:
        return None
    least = sum(r.step_roofline_s(pos) for pos in r.traced_positions)
    return 100.0 * least / s["busy_s"]
