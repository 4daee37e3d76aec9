"""Time per batch in which the device waits on the host: within each
batch's span (start of ``bench.upload`` to end of ``bench.fetch``),
the time no device op ran; median over the traced batches, in ms."""
import numpy as np


def read(rec):
    up = rec.trace.spans.get("bench.upload", [])
    fetch = rec.trace.spans.get("bench.fetch", [])
    n = min(len(up), len(fetch))
    if n == 0:
        return None
    busy = rec.trace.busy_intervals
    idle = []
    for u, f in zip(up[:n], fetch[:n]):
        lo, hi = u[0], f[1]
        covered = sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in busy
                      if e > lo and s < hi)
        idle.append(hi - lo - covered)
    return float(np.median(idle) / 1e6)
