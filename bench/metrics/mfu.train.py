"""6 N FLOPs per trained token (``harness.flops.train_flops_per_token``) times
the tokens trained in the window, over window time and the chip's bf16 peak,
in percent. Recomputation is not counted."""


def read(r):
    if r.peaks is None or not r.counters.get("model_flops"):
        return None
    return r.counters["model_flops"] / (r.window_s * r.peaks.flops_bf16) * 100.0
