"""Device-op events in the traced window over the fused-graph calls made in
it, per chip (``harness.trace.reduce``'s op count)."""


def read(r):
    if r.trace is None or not r.trace.n_chips or not r.counters.get("calls"):
        return None
    return r.trace.n_ops / r.trace.n_chips / r.counters["calls"]
