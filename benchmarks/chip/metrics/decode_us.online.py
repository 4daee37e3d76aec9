"""Device time of the MGQE decode kernels (``mgqe_decode`` ops in the
trace) per request, in us."""
KERNEL = "mgqe_decode"


def read(rec):
    n = len(rec.trace.spans.get("bench.call", []))
    if n == 0 or rec.trace.kernel_calls.get(KERNEL, 0) == 0:
        return None
    return rec.trace.kernel_ns[KERNEL] / n / 1e3
