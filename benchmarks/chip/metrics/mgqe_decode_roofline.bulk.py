"""Roofline share of the MGQE decode kernels, in %: the least time
their bytes (``work.mgqe_decode_bytes`` per call) take at the chip's
HBM bandwidth, over their device time in the trace.  Not clamped: a
reading over 100 means the bytes or the time are miscounted."""
KERNEL = "mgqe_decode"


def read(rec):
    calls = rec.trace.kernel_calls.get(KERNEL, 0)
    if calls == 0:
        return None
    m = rec.model
    per_call = rec.work.mgqe_decode_bytes(
        rec.traffic["batch"], m["num_subspaces"], m["num_centroids"],
        m["embed_dim"] // m["num_subspaces"])
    bound_s = calls * per_call / rec.peaks["hbm_bytes_per_s"]
    return 100.0 * bound_s / (rec.trace.kernel_ns[KERNEL] / 1e9)
