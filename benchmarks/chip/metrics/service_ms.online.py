"""Host-side service time of one request: from the start of its
``bench.upload`` span to the end of its ``bench.fetch`` span, median
over the requests of the traced window, in ms."""
import numpy as np


def read(rec):
    up = rec.trace.spans.get("bench.upload", [])
    fetch = rec.trace.spans.get("bench.fetch", [])
    n = min(len(up), len(fetch))
    if n == 0:
        return None
    return float(np.median([f[1] - u[0] for u, f in zip(up[:n], fetch[:n])])
                 / 1e6)
