"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals) / window, averaged over the chips used,
in percent (``harness.trace.reduce``)."""


def read(r):
    share = None if r.trace is None else r.trace.idle_share
    return None if share is None else share * 100.0
