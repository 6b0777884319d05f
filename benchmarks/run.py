"""Benchmark harness: one module per paper figure.
Prints ``name,value,derived`` CSV rows; claim checks appear as
``claim/<name>,PASS|FAIL``. Usage::

    PYTHONPATH=src python -m benchmarks.run [--smoke] [--out bench.csv]

``--smoke`` runs the fast subset only (the CI job). ``--out`` mirrors every
CSV row to a file (uploaded as a CI artifact). The exit code is the number
of failed claims plus crashed modules — CI gates on it directly instead of
grepping the output (shell ``! grep`` masks pipeline errors under
``pipefail``).

Every module, the paper figure it reproduces, how to run it standalone,
and its pass thresholds are documented in ``docs/BENCHMARKS.md``.
"""
import importlib
import sys
import time

from benchmarks import common

MODULES = [
    "benchmarks.fig2_fs_overhead",
    "benchmarks.fig7a_offload_levels",
    "benchmarks.fig7b_prep_ratio",
    "benchmarks.fig8_db_scalability",
    "benchmarks.fig9_prep_scalability",
    "benchmarks.fig10_designs",
    "benchmarks.fig11_latency_throughput",
    "benchmarks.fig12_cache_timeline",
    "benchmarks.fig13_cache_pollution",
    "benchmarks.fig14_sharded_plane",
    "benchmarks.fig15_async_wal",
    "benchmarks.fig16_striped_extents",
    "benchmarks.fig17_rebalance",
    "benchmarks.fig18_prep_pipeline",
    "benchmarks.fig19_router_failover",
    "benchmarks.fig20_kv_serving",
    "benchmarks.fig21_pushdown",
    "benchmarks.fig22_memtier",
]

SMOKE_MODULES = [
    "benchmarks.fig2_fs_overhead",
    "benchmarks.fig14_sharded_plane",
    "benchmarks.fig15_async_wal",
    "benchmarks.fig16_striped_extents",
    "benchmarks.fig17_rebalance",
    "benchmarks.fig18_prep_pipeline",
    "benchmarks.fig19_router_failover",
    "benchmarks.fig20_kv_serving",
    "benchmarks.fig21_pushdown",
    "benchmarks.fig22_memtier",
]

USAGE = """\
usage: PYTHONPATH=src python -m benchmarks.run [--smoke] [--out FILE]

  --smoke   fast subset only (the CI bench-smoke job)
  --out F   mirror every CSV row to F (uploaded as a CI artifact)

Exit code = failed claims + crashed modules. Per-figure documentation
(paper figure, how to run standalone, pass thresholds): docs/BENCHMARKS.md
"""


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if "--help" in argv or "-h" in argv:
        print(USAGE)
        return 0
    modules = SMOKE_MODULES if "--smoke" in argv else MODULES
    if "--out" in argv:
        i = argv.index("--out")
        if i + 1 >= len(argv) or argv[i + 1].startswith("-"):
            print(USAGE, file=sys.stderr)
            return 2
        common.OUT = open(argv[i + 1], "w")
    t0 = time.time()
    crashes = 0
    for mod in modules:
        print(f"# === {mod} ===", flush=True)
        t = time.time()
        try:
            importlib.import_module(mod).main()
        except Exception as e:  # noqa: BLE001
            common.emit(f"claim/{mod}/crashed", "FAIL",
                        f"{type(e).__name__}: {e}")
            crashes += 1
        print(f"# {mod} took {time.time()-t:.1f}s", flush=True)
    print(f"# total {time.time()-t0:.1f}s "
          f"({common.FAILURES} failed claims, {crashes} crashes)")
    if common.OUT is not None:
        common.OUT.close()
    return min(crashes + common.FAILURES, 125)


if __name__ == "__main__":
    sys.exit(main())
