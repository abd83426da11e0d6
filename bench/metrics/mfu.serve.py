"""Model FLOPs of every prompt and generated token processed in the window
(``harness.flops.serve_batch_flops``) over window time and the chip's bf16
peak, in percent."""


def read(r):
    if r.peaks is None or not r.counters.get("model_flops"):
        return None
    return r.counters["model_flops"] / (r.window_s * r.peaks.flops_bf16) * 100.0
