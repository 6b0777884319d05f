"""The program's spans and time counters over one cell's traced window.

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s> --trace-dir <dir>

Runs the cell as ``bench/run.py --trace 1`` does, with the program's own
spans and time counters on (``repro.obs``) for the whole run, set-up
included, keeps the trace in ``--trace-dir``, and prints one JSON line
last on stdout: the harness's result under ``"result"`` and, under
``"phases"``:

- ``span_ms``: per span name (the harness's and the program's), the mean
  in ms and the count of the spans that start inside the window, on any
  host thread;
- ``idle_gaps``: device idle time by the innermost span over each gap on
  the thread that opened ``bench.window`` (the program's phase spans
  included), where the harness's reduction knows only its own spans;
- ``counters``: the window's change of the fabric's ``pickle_s`` and the
  file system's ``lease_s`` and ``lease_records``;
- ``metrics``: the readings a per-layer metric of each name would give;
- ``ops_per_s`` of the traced window and ``spans_per_op``.

The harness reads none of this yet: ``harness.run_cell`` does not switch
the program's spans on, and ``harness.SPAN_NAMES`` holds only its own.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from types import SimpleNamespace
from typing import Dict, Optional, Sequence, Tuple

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# per-layer readings: the mean of one program span, in ms
SPAN_MEANS = {
    "kv_pack_ms": "kvstore.put.pack",
    "kv_commit_ms": "kvstore.put.commit",
    "kv_unpack_ms": "kvstore.fetch.unpack",
    "scan_plan_ms": "lsm.scan.plan",
    "scan_merge_ms": "lsm.scan.merge",
    "pushdown_read_ms": "pushdown.read",
}
# per-layer readings: a time counter's change over the window, in ms per
# completed op
COUNTERS_PER_OP = {
    "fabric_pickle_ms": "pickle_s",
    "lease_ms_per_op": "lease_s",
}


def window_line(profile):
    """The host line (thread) that holds the ``bench.window`` span."""
    from bench.tracereduce import WINDOW_SPAN

    found = [line for plane in profile.planes
             if plane.name.startswith("/host:") for line in plane.lines
             if any(e.name == WINDOW_SPAN for e in line.events)]
    if len(found) != 1:
        raise ValueError(f"{len(found)} host lines hold {WINDOW_SPAN!r}")
    return found[0]


def on_window_thread(profile):
    """The profile with its host planes cut to the window's line: the
    harness's reduction then labels each idle gap from that thread's
    spans alone, never from a fabric worker's."""
    line = window_line(profile)
    planes = [p for p in profile.planes if not p.name.startswith("/host:")]
    planes.append(SimpleNamespace(name="/host:window", lines=[line]))
    return SimpleNamespace(planes=planes)


def span_totals(profile, names: Sequence[str], window: Tuple[float, float]
                ) -> Dict[str, Tuple[float, int]]:
    """Summed ns and count of the spans of each name that start inside
    ``window``, on any host thread."""
    wanted, out = set(names), {}
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in wanted and window[0] <= e.start_ns < window[1]:
                    ns, n = out.get(e.name, (0.0, 0))
                    out[e.name] = (ns + e.duration_ns, n + 1)
    return out


def reduce(profile, names: Sequence[str]):
    """(the harness's reduction with gaps labelled on the window's thread
    by ``names``, span totals of ``names`` on every thread)."""
    from bench import tracereduce

    red = tracereduce.reduce(on_window_thread(profile), names)
    return red, span_totals(profile, names, red.window)


def span_mean_ms(totals: Dict[str, Tuple[float, int]], name: str
                 ) -> Optional[float]:
    ns, n = totals.get(name, (0.0, 0))
    return ns / n * 1e-6 if n else None


def readings(totals: Dict[str, Tuple[float, int]], counters: Dict[str, float],
             done: int) -> Dict[str, float]:
    """What each metric of ``SPAN_MEANS`` and ``COUNTERS_PER_OP`` reads;
    a metric with nothing to read is left out."""
    out = {m: span_mean_ms(totals, s) for m, s in SPAN_MEANS.items()}
    for m, key in COUNTERS_PER_OP.items():
        out[m] = counters[key] * 1e3 / done if done else None
    return {m: v for m, v in out.items() if v is not None}


def plane_counters(plane) -> Dict[str, float]:
    """The counters of a plane's fabric and file system: the time ones,
    and the lease-journal records."""
    fabric, fs = plane
    return {"pickle_s": fabric.pickle_s, "lease_s": fs.lease_s,
            "lease_records": fs.lease_records}


def measure(cell, *, seed: int, seconds: float, trace_dir: str,
            t_start: float, **kw) -> dict:
    """One traced run of ``cell`` with the program's spans on; ``kw`` goes
    to ``harness.run_cell``."""
    from bench import harness, tracereduce
    from repro import obs

    obs.enable(True)
    planes = []

    def capture(dep):  # after set-up: the window's counters start here
        fs = dep.fs if hasattr(dep, "fs") else dep.db.fs
        planes.append(((dep.fabric, fs), plane_counters((dep.fabric, fs))))

    try:
        out = harness.run_cell(cell, seed=seed, seconds=seconds, trace=True,
                               t_start=t_start, trace_dir=trace_dir,
                               patch=capture, **kw)
    finally:
        obs.enable(False)
    (plane, start), = planes
    counters = {k: v - start[k] for k, v in plane_counters(plane).items()}
    (path,) = [os.path.join(d, f) for d, _, files in os.walk(trace_dir)
               for f in files if f.endswith(".xplane.pb")]
    red, totals = reduce(tracereduce.load(path),
                         harness.SPAN_NAMES + obs.SPANS)
    done = out["attempted"] - out["failed"]
    emitted = sum(n for k, (_, n) in totals.items() if k in obs.SPANS)
    phases = {
        "ops_done": done, "window_s": red.window_s,
        "ops_per_s": done / red.window_s,
        "spans_per_op": emitted / done if done else None,
        "span_ms": {k: [span_mean_ms(totals, k), n]
                    for k, (_, n) in sorted(totals.items())},
        "idle_gaps": [[k, v] for k, v in red.idle_by_span()],
        "counters": counters,
        "metrics": readings(totals, counters, done),
    }
    return {"result": out, "phases": phases}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-dir", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    cell = harness.resolve(args.workload)
    harness.configure_cache()
    out = measure(cell, seed=args.seed, seconds=args.seconds,
                  trace_dir=args.trace_dir, t_start=T_START)
    harness.log(f"phases: {json.dumps(out['phases'])}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
