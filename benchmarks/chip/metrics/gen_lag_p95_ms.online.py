"""How late the load generator sent requests: the 95th percentile of
(send - due) over the requests due after the previous one had
completed, in ms (host clock)."""
import numpy as np


def read(rec):
    lag = rec.stats.get("gen_lag_s")
    if lag is None or len(lag) == 0:
        return None
    return float(np.percentile(lag, 95) * 1e3)
