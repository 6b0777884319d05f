"""Device time of one prefill: the prefill program's events in the trace
(``jit_prefill_step``, both prompt lengths), per call."""


def read(run):
    if run.trace is None:
        return None
    secs, calls = run.trace.module_time_s("prefill_step")
    return secs / calls * 1e3 if calls else None
