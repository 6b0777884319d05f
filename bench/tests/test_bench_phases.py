"""``bench/phases.py``: the program's spans over a traced window. Idle gaps
take their labels from the window's thread alone, span totals count every
thread, the readings' arithmetic, and, on traces recorded on a TPU v5e
(``bench/testdata/``), the harness's own numbers left exactly as its
reduction gives them."""
from __future__ import annotations

import os
import sys
from types import SimpleNamespace as NS

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, phases, tracereduce as tr  # noqa: E402
from repro import obs  # noqa: E402

NAMES = harness.SPAN_NAMES + obs.SPANS


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _two_threads():
    """The window's thread fetches over (10, 60); a fabric worker runs a
    batch over (20, 50) and one more after the window; the device is
    busy over (40, 45) and (80, 90)."""
    client = NS(name="python", events=[
        _ev("bench.window", 0, 100), _ev("kv.fetch", 10, 50),
        _ev("kvstore.fetch", 11, 48), _ev("kvstore.fetch.wire", 12, 30),
        _ev("kvstore.fetch.unpack", 44, 14)])
    worker = NS(name="rpc-worker-0", events=[
        _ev("fabric.batch", 20, 30), _ev("pushdown.read", 70, 5),
        _ev("fabric.batch", 120, 10)])
    host = NS(name="/host:CPU", lines=[client, worker])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[_ev("fusion.1", 40, 5),
                                   _ev("fusion.2", 80, 10)]),
        NS(name="XLA Modules", events=[])])
    return NS(planes=[host, dev])


def test_gaps_take_labels_from_the_window_thread_only():
    red, totals = phases.reduce(_two_threads(), NAMES)
    # (0,40): mid 20 in the wire phase, where the worker's batch is the
    # latest-starting span on any thread; (45,80): mid 62.5 in none on
    # the window's thread, though the worker reads at 70; (90,100): none
    assert dict(red.idle_by_span()) == {
        "kvstore.fetch.wire": pytest.approx(40e-9),
        tr.NO_SPAN: pytest.approx(45e-9)}
    plain = tr.reduce(_two_threads(), NAMES)
    assert dict(plain.idle_by_span())["fabric.batch"] == pytest.approx(40e-9)
    assert red.busy_ns == plain.busy_ns == 15


def test_span_totals_count_every_thread_inside_the_window():
    _, totals = phases.reduce(_two_threads(), NAMES)
    assert totals["fabric.batch"] == (30, 1)  # the one at 120 is outside
    assert totals["pushdown.read"] == (5, 1)
    assert totals["kvstore.fetch.wire"] == (30, 1)
    assert phases.span_mean_ms(totals, "kv.fetch") == pytest.approx(50e-6)
    assert phases.span_mean_ms(totals, "lsm.scan") is None


def test_one_window_thread_is_required():
    p = _two_threads()
    p.planes[0].lines[1].events.append(_ev("bench.window", 0, 5))
    with pytest.raises(ValueError):
        phases.window_line(p)


def test_readings_arithmetic():
    ms = 1e6  # ns
    totals = {"kvstore.put.pack": (3 * 120 * ms, 3),
              "kvstore.fetch.unpack": (40 * ms, 2),
              "lsm.scan.plan": (0.0, 0)}
    got = phases.readings(totals, {"pickle_s": 1.5, "lease_s": 0.25}, 10)
    assert got == {"kv_pack_ms": pytest.approx(120.0),
                   "kv_unpack_ms": pytest.approx(20.0),
                   "fabric_pickle_ms": pytest.approx(150.0),
                   "lease_ms_per_op": pytest.approx(25.0)}
    # no op done: nothing per op; no span of a name: no mean
    assert phases.readings(totals, {"pickle_s": 1.5, "lease_s": 0.25},
                           0) == {"kv_pack_ms": pytest.approx(120.0),
                                  "kv_unpack_ms": pytest.approx(20.0)}


def test_every_reading_names_a_program_span():
    assert set(phases.SPAN_MEANS.values()) <= set(obs.SPANS)


def test_counters_of_a_plane():
    plane = (NS(pickle_s=0.5), NS(lease_s=0.125, lease_records=7))
    assert phases.plane_counters(plane) == {
        "pickle_s": 0.5, "lease_s": 0.125, "lease_records": 7}


@pytest.fixture(scope="module")
def scan_short():
    """3 s of scan-short traced on one TPU v5e before the program had spans."""
    return tr.load(os.path.join(harness.BENCH, "testdata",
                                "scan-short.xplane.pb"))


@pytest.mark.parametrize("names", [harness.SPAN_NAMES, NAMES])
def test_scan_short_reduction_unchanged(scan_short, names):
    """With only the harness's spans in the trace, the window's thread
    gives every number the harness's reduction gives."""
    plain = tr.reduce(scan_short, harness.SPAN_NAMES)
    red, totals = phases.reduce(scan_short, names)
    assert (red.window, red.busy_ns, red.ops_ns, red.modules_ns,
            red.module_calls) == (plain.window, plain.busy_ns, plain.ops_ns,
                                  plain.modules_ns, plain.module_calls)
    assert red.breakdown() == plain.breakdown()
    assert red.idle_share == plain.idle_share
    assert not set(totals) & set(obs.SPANS)
    assert totals["bench.window"][1] == 1 and totals["db.scan"][1] > 0


@pytest.fixture(scope="module")
def kv_attach():
    """3.4 s of kv-attach traced on one TPU v5e with the program's spans
    on (``bench/phases.py``, seed 3131000002): five hits and a miss."""
    import gzip

    from jax.profiler import ProfileData

    with gzip.open(os.path.join(harness.BENCH, "testdata",
                                "kv-attach.xplane.pb.gz"), "rb") as f:
        return ProfileData.from_serialized_xspace(f.read())


def test_kv_attach_gaps_split_by_phase(kv_attach):
    red, _ = phases.reduce(kv_attach, NAMES)
    plain = tr.reduce(kv_attach, harness.SPAN_NAMES)
    assert (red.window, red.busy_ns) == (plain.window, plain.busy_ns) == (
        (46142196.0, 3431838538.0), 575869277.0)
    idle, before = dict(red.idle_by_span()), dict(plain.idle_by_span())
    assert sum(idle.values()) == pytest.approx(sum(before.values()))
    # the harness's labels hold the store's idle time; the phases take it
    assert before["kv.fetch"] + before["kv.put"] > 0.99 * sum(before.values())
    coarse = ("kv.fetch", "kv.put", "kvstore.fetch", "kvstore.put")
    assert sum(idle.get(k, 0.0) for k in coarse) < 0.1 * sum(idle.values())
    assert [k for k, _ in red.idle_by_span()[:4]] == [
        "kvstore.fetch.wire", "kvstore.fetch.assemble", "kvstore.put.wire",
        "kvstore.fetch.unpack"]


def test_kv_attach_readings(kv_attach):
    _, totals = phases.reduce(kv_attach, NAMES)
    got = phases.readings(totals, {"pickle_s": 0.0, "lease_s": 0.0}, 6)
    assert got == {"kv_pack_ms": pytest.approx(181.016515),
                   "kv_commit_ms": pytest.approx(26.086898),
                   "kv_unpack_ms": pytest.approx(18.52047333),
                   "fabric_pickle_ms": 0.0, "lease_ms_per_op": 0.0}
    assert [totals[k][1] for k in ("kv.fetch", "kvstore.fetch", "kv.put",
                                   "kvstore.put", "fabric.batch")] == [
        6, 6, 1, 1, 28]


@pytest.mark.parametrize("op", ["fetch", "put"])
def test_kv_attach_phases_cover_the_span(kv_attach, op):
    """The phases' means sum to within 10 % of the benchmark's wrap."""
    _, totals = phases.reduce(kv_attach, NAMES)
    parts = [k for k in obs.SPANS if k.startswith(f"kvstore.{op}.")]
    assert len(parts) == 4
    whole = phases.span_mean_ms(totals, f"kv.{op}")
    assert 0.9 * whole <= sum(phases.span_mean_ms(totals, k)
                              for k in parts) <= whole
