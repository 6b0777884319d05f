"""Model FLOPs of the hybrid's prefill and decode tokens that the window
completed (``bench/hybrid_cost.py``), over the window's length times the
chip's bf16 peak (``bench/peaks.py``), in percent."""


def read(run):
    if run.peaks is None or not run.flops:
        return None
    return run.flops / (run.window_s * run.peaks["bf16_flops"]) * 100.0
