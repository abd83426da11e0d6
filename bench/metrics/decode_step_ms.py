"""Mean wall time of one decode step of ``serve_batch``'s host loop: the sum
of its ``decode_s`` over the sum of decode steps, for every batch of the
window (program span)."""


def read(r):
    c = r.counters
    return c["decode_s"] / c["decode_steps"] * 1e3 if c.get("decode_steps") else None
