"""What a measured window leaves for the metric readers: every token's
arrival on the host, reduced to the lists the readers need, and the
trace summary of a traced run with the steps it served."""
from __future__ import annotations

from dataclasses import dataclass, field

from chipbench import counts


@dataclass
class Record:
    dims: dict               # weights.dims of the configuration
    peaks: dict              # peaks.PEAKS entry of the chip
    batch: int
    prompt_len: int
    setup_s: float
    window_s: float = 0.0    # window start to its last token
    tokens: int = 0          # output tokens that reached the host
    ttft_s: list = field(default_factory=list)     # one per request
    prefill_s: list = field(default_factory=list)  # one per batch
    steps: list = field(default_factory=list)      # (position, seconds)
    trace: dict | None = None                      # tracing.reduce
    # what the traced segment served: one prefill per batch it started,
    # and the position each of its decode steps wrote
    traced_prefills: int = 0
    traced_positions: list = field(default_factory=list)

    def prefill_flops(self) -> int:
        return counts.prefill_flops(self.dims, self.batch, self.prompt_len)

    def step_roofline_s(self, pos: int) -> float:
        return counts.decode_roofline_s(self.dims, self.peaks, self.batch,
                                        pos)


def window_record(rec: Record, batches, t_start: float,
                  t_close: float) -> Record:
    """Fill ``rec`` from the batches served in [t_start, t_close]."""
    rec.window_s = t_close - t_start
    for b in batches:
        times = [t for t in b.times if t <= t_close]
        if not times:
            continue
        rec.tokens += b.prompt.shape[0] * len(times)
        first = times[0] - b.t0
        rec.prefill_s.append(first)
        rec.ttft_s.extend([first] * b.prompt.shape[0])
        # the token after step j is written at position prompt_len + j
        rec.steps.extend((rec.prompt_len + j, t1 - t0) for j, (t0, t1)
                         in enumerate(zip(times, times[1:])))
    return rec
