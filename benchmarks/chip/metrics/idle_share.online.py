"""Share of the traced window in which no operation ran on the device,
in % (1 - union of device-op intervals / window)."""


def read(rec):
    return 100.0 * rec.trace.idle_share
