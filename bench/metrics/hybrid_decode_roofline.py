"""The hybrid's decode step against its HBM roofline, in percent: the
least bytes a step moves (``hybrid_cost.decode_bytes``, averaged over the
decode steps of the window's finished requests) over the device time per
call of the decode program (``jit_decode_step``) times the chip's HBM
bandwidth. A B=1 decode step is bound by its bytes, not its FLOPs."""
from bench import hybrid_cost


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    secs, calls = run.trace.module_time_s("decode_step")
    steps = run.cell.mix["output_tokens"] - 1
    done = run.done
    if not calls or not secs or not done or steps < 1:
        return None
    conf = run.cell.config
    per_step = sum(hybrid_cost.request_decode_bytes(
        conf, r.op.prompt_len, steps + 1) for r in done) / (steps * len(done))
    return per_step / (secs / calls * run.peaks["hbm_bytes_per_s"]) * 100.0
