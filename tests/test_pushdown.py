"""Pushdown operator plane — verifier fuzz, defense in depth, E2E
correctness, and the agreement of the offload API's submit spellings.

The differential property itself (pushdown ≡ block shipping ≡ dict model
on random corpora/programs) lives in tests/test_property.py with its
seeded mirror in tests/test_invariants_fallback.py; this file covers the
crafted scenarios those generators would only hit by luck:

  * every malformed-program class is rejected with ProgramError at submit
    time — and the ENGINE independently re-verifies, so a program that
    skips the initiator's API dies on the target before any block is read;
  * LSM shadowing across stripes: a newer non-matching overwrite (or
    tombstone) on one target suppresses an older matching version on
    another;
  * the single-stripe aggregate fast path ships only aggregate state;
  * every spelling of ``submit`` (one spec, a list, ``async_=True``, the
    positional single-task form) returns the same ``(result, where)``.
"""
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import pushdown as P  # noqa: E402
from repro.core.admission import AcceptAll  # noqa: E402
from repro.core.blockdev import BLOCK_SIZE, BlockDevice  # noqa: E402
from repro.core.engine import OffloadEngine  # noqa: E402
from repro.core.fs import OffloadFS  # noqa: E402
from repro.core.lsm.db import DBConfig, OffloadDB  # noqa: E402
from repro.core.offloader import TaskOffloader, serve_engine  # noqa: E402
from repro.core.rpc import RpcFabric  # noqa: E402

from pushdown_util import build_plane  # noqa: E402


def wait_no_leases(fs, timeout=5.0):
    deadline = time.time() + timeout
    while fs._leases and time.time() < deadline:
        time.sleep(0.002)
    assert not fs._leases


# ---------------------------------------------------- verifier: accepts
def test_builders_produce_verified_programs():
    prog = P.build_scan(b"a", b"z", where=P.and_(
        P.prefix(P.value(), b"A"),
        P.not_(P.contains(P.key(), b"tmp")),
        P.cmp("lt", P.length(P.value()), P.lit(100)),
    ))
    assert P.verify_program(prog) is prog
    assert P.eval_filter(prog, b"k1", b"Axx")
    assert not P.eval_filter(prog, b"k1tmp", b"Axx")
    assert not P.eval_filter(prog, b"k1", b"Bxx")


def test_repeated_leaf_nodes_are_not_shared_structure():
    # CPython interns small constant tuples: every ("value",) leaf is the
    # same object. Only composite re-use is rejected.
    prog = P.build_scan(where=P.or_(P.prefix(P.value(), b"A"),
                                    P.prefix(P.value(), b"B"),
                                    P.cmp("eq", P.value(), P.value())))
    assert P.verify_program(prog) is prog


# ---------------------------------------------------- verifier: rejects
def mk(**over):
    base = {"v": 1, "lo": b"", "hi": None,
            "filter": None, "project": None, "aggregate": None}
    base.update(over)
    return base


class _Unpicklable(bytes):
    def __reduce__(self):
        raise RuntimeError("nope")


def _nested_not(n):
    e = ("cmp", "eq", ("key",), ("key",))
    for _ in range(n):
        e = ("not", e)
    return e


BAD_PROGRAMS = [
    ("not_a_dict", 17),
    ("bad_version", mk(v=2)),
    ("missing_version", {"lo": b"", "hi": None}),
    ("unknown_key", mk(exec="rm -rf /")),
    ("lo_not_bytes", mk(lo="a")),
    ("hi_not_bytes", mk(hi=5)),
    ("oversized_bound", mk(lo=b"x" * 2000)),
    ("unknown_projection", mk(project="rows")),
    ("unknown_aggregate", mk(aggregate="sum")),
    ("aggregate_and_project", mk(project="key", aggregate="count")),
    ("bool_literal", mk(filter=("lit", True))),
    ("non_bool_filter", mk(filter=("lit", 5))),
    ("unknown_operator", mk(filter=("syscall", "rm"))),
    ("code_not_data", mk(filter=len)),
    ("callable_literal", mk(filter=("lit", len))),
    ("type_confusion", mk(filter=("cmp", "lt", ("key",), ("lit", 5)))),
    ("cmp_over_bool",
     mk(filter=("cmp", "eq", ("prefix", ("key",), ("lit", b"a")),
                ("prefix", ("key",), ("lit", b"b"))))),
    ("unknown_cmp", mk(filter=("cmp", "spaceship", ("key",), ("key",)))),
    ("len_of_int", mk(filter=("len", ("lit", 5)))),
    ("and_of_ints", mk(filter=("and", ("lit", 1), ("lit", 2)))),
    ("arity_wrong", mk(filter=("not", ("lit", 1), ("lit", 2)))),
    ("empty_node", mk(filter=())),
    ("oversized_literal",
     mk(filter=("prefix", ("value",), ("lit", b"A" * 2000)))),
    ("too_deep", mk(filter=_nested_not(13))),
    ("too_many_nodes",
     mk(filter=("or",) + tuple(("prefix", ("value",), ("lit", bytes([c])))
                               for c in range(64)))),
    ("oversized_pickle",
     mk(filter=("or",) + tuple(("prefix", ("value",), ("lit", bytes(500)))
                               for _ in range(40)))),
    ("unpicklable_payload",
     mk(filter=("prefix", ("value",), ("lit", _Unpicklable(b"A"))))),
]


@pytest.mark.parametrize("name,prog", BAD_PROGRAMS,
                         ids=[n for n, _ in BAD_PROGRAMS])
def test_verifier_rejects(name, prog):
    with pytest.raises(P.ProgramError):
        P.verify_program(prog)


def test_verifier_rejects_shared_composite_substructure():
    sub = P.not_(P.prefix(P.value(), b"A"))
    with pytest.raises(P.ProgramError, match="cyclic or shared"):
        P.build_scan(where=P.and_(sub, sub))


# ----------------------------------------------------- defense in depth
def test_malformed_program_rejected_before_anything_ships():
    fs, fabric, engines, db = build_plane(2)
    db.put(b"k0001", b"Av")
    db.flush_all()
    fabric.drain()
    b0 = fabric.total_bytes()
    with pytest.raises(P.ProgramError):
        db.scan(program=mk(filter=("syscall", "rm")), pushdown=True)
    fabric.drain()
    assert fabric.total_bytes() == b0  # nothing crossed the wire
    assert db.stats["pushdown_scans"] == 0
    assert not fs._leases


def test_engine_independently_reverifies_program():
    """A compromised initiator that skips its own API and ships an
    unverified program over the raw fabric dies on the TARGET's verifier
    before any block is read."""
    fs, fabric, engines, db = build_plane(1)
    for i in range(8):
        db.put(f"k{i:04d}".encode(), b"Av" * 10)
    db.flush_all()
    tid = db.levels[0][-1]
    ino = fs.stat(db.tables[tid].path)
    tables = [{"runs": [(e.block, e.nblocks) for e in ino.extents],
               "size": ino.size, "rank": 3}]
    # reprolint: allow[lease-raw] test hand-builds wire authorization from a raw grant; released in-test
    lease = fs.grant_lease(ino.extents, ())
    wire = {"task_id": lease.task_id,
            "read_blocks": sorted(lease.read_blocks), "write_blocks": []}
    evil = mk(filter=("syscall", "rm -rf /"))
    with pytest.raises(P.ProgramError):
        fabric.call("init0", "storage0", "submit_task", "init0",
                    "pushdown_scan", wire, (tables, evil),
                    {"final": False}, ino.mtime, False)
    assert engines[0].pushdown_scans == 0  # died before the scan counter
    assert engines[0].pushdown_rows_in == 0
    fs.release_lease(lease)
    assert not fs._leases
    # the same lease/table shape with a VERIFIED program works fine
    # reprolint: allow[lease-raw] test hand-builds wire authorization from a raw grant; released in-test
    lease = fs.grant_lease(ino.extents, ())
    wire = {"task_id": lease.task_id,
            "read_blocks": sorted(lease.read_blocks), "write_blocks": []}
    ok = P.build_scan(where=P.prefix(P.value(), b"A"))
    status, (kind, matched, markers, scanned) = fabric.call(
        "init0", "storage0", "submit_task", "init0", "pushdown_scan",
        wire, (tables, ok), {"final": False}, ino.mtime, False)
    assert status == "ok" and kind == "rows" and scanned == 8
    assert [k for k, _, _ in matched] == sorted(f"k{i:04d}".encode()
                                                for i in range(8))
    fs.release_lease(lease)


# ------------------------------------------------------ E2E correctness
def test_shadowing_across_stripes_suppresses_stale_matches():
    """The unsound-naive-filter scenario: the newer version of a key does
    NOT match the filter (overwrite or tombstone) and lives in a different
    SSTable — possibly a different stripe — than the older matching
    version. Remote filtering must not resurrect the old row."""
    fs, fabric, engines, db = build_plane(2)
    db.put(b"hot0001", b"A" * 24)   # will be overwritten with non-matching
    db.put(b"dead001", b"A" * 24)   # will be tombstoned
    db.put(b"live001", b"A" * 24)   # stays
    db.flush_all()                  # table 1
    db.put(b"hot0001", b"Z" * 24)
    db.delete(b"dead001")
    db.flush_all()                  # table 2, next stripe
    prog = P.build_scan(where=P.prefix(P.value(), b"A"))
    expect = [(b"live001", b"A" * 24)]
    assert db.scan(program=prog, pushdown=False) == expect
    assert db.scan(program=prog, pushdown=True) == expect
    # the newest version in the MEMTABLE must shadow both tables too
    db.put(b"live001", b"Z" * 24)
    db.put(b"hot0001", b"A" * 24)
    expect = [(b"hot0001", b"A" * 24)]
    assert db.scan(program=prog, pushdown=False) == expect
    assert db.scan(program=prog, pushdown=True) == expect
    wait_no_leases(fs)
    # the engines really ran the sub-scans (visible through ping too)
    total = sum(fabric.call("init0", e.node, "ping")["pushdown_scans"]
                for e in engines)
    assert total == sum(e.pushdown_scans for e in engines) > 0


def test_projection_aggregate_and_limit_match_local():
    fs, fabric, engines, db = build_plane(2)
    for i in range(30):
        tag = b"A" if i % 3 == 0 else b"B"
        db.put(f"k{i:04d}".encode(), tag + bytes(i))
    db.flush_all()
    where = P.prefix(P.value(), b"A")
    for kw in ({"project": "key"}, {"project": "value"}, {"project": "row"},
               {"aggregate": "count"}, {"aggregate": "bytes"},
               {"aggregate": "min_key"}, {"aggregate": "max_key"}):
        prog = P.build_scan(b"k0002", b"k0028", where=where, **kw)
        assert (db.scan(program=prog, pushdown=True)
                == db.scan(program=prog, pushdown=False))
    prog = P.build_scan(where=where)
    assert (db.scan(n=4, program=prog, pushdown=True)
            == db.scan(n=4, program=prog, pushdown=False))
    assert len(db.scan(n=4, program=prog, pushdown=True)) == 4


def test_single_stripe_aggregate_ships_only_state():
    fs, fabric, engines, db = build_plane(1)
    for i in range(50):
        db.put(f"k{i:04d}".encode(), b"A" + bytes(64))
    db.flush_all()  # memtable empty → the sub-scan covers the whole range
    rows_prog = P.build_scan()
    agg_prog = P.build_scan(aggregate="count")
    fabric.drain()
    b0 = fabric.total_bytes()
    assert db.scan(program=rows_prog, pushdown=True) == \
        db.scan(program=rows_prog, pushdown=False)
    fabric.drain()
    rows_wire = fabric.total_bytes() - b0
    b1 = fabric.total_bytes()
    assert db.scan(program=agg_prog, pushdown=True) == 50 == \
        db.scan(program=agg_prog, pushdown=False)
    fabric.drain()
    agg_wire = fabric.total_bytes() - b1
    assert agg_wire < rows_wire / 4  # state only, no rows, no markers


def test_pushdown_flag_degrades_gracefully_without_engines():
    expect = [(f"k{i:04d}".encode(), b"A") for i in range(1, 10, 2)]
    prog = P.build_scan(where=P.prefix(P.value(), b"A"))
    # no offloader at all → the program evaluates on the initiator
    dev = BlockDevice(num_blocks=1 << 14)
    fs = OffloadFS(dev, node="init0")
    db = OffloadDB(fs, None, DBConfig(memtable_bytes=4 * 1024))
    for i in range(10):
        db.put(f"k{i:04d}".encode(), b"A" if i % 2 else b"B")
    assert db.scan(program=prog, pushdown=True) == expect
    assert db.stats["pushdown_scans"] == 0  # never planned as pushdown
    # an offloader but a memtable-only corpus: the pushdown plan runs,
    # finds no SSTables to ship, and answers from the initiator stream
    dev2 = BlockDevice(num_blocks=1 << 14)
    fs2 = OffloadFS(dev2, node="init0")
    off = TaskOffloader(fs2, RpcFabric(), node="init0", targets=[])
    db2 = OffloadDB(fs2, off, DBConfig(memtable_bytes=4 * 1024))
    for i in range(10):
        db2.put(f"k{i:04d}".encode(), b"A" if i % 2 else b"B")
    assert db2.scan(program=prog, pushdown=True) == expect
    assert db2.stats["pushdown_scans"] == 1  # planned, zero sub-scans
    assert not fs2._leases


# ------------------------------------------------- submit spellings
def _stub_sum(io, block, nblocks):
    return sum(io.offload_read(block, nblocks)) % 65536


def _offload_plane():
    dev = BlockDevice(num_blocks=1 << 12)
    fs = OffloadFS(dev, node="init0")
    fabric = RpcFabric()
    eng = OffloadEngine(fs, node="storage0", enable_cache=False)
    eng.register_stub("sum", _stub_sum)
    serve_engine(eng, fabric, AcceptAll())
    off = TaskOffloader(fs, fabric, node="init0", targets=["storage0"])
    off.register_local_stub("sum", _stub_sum)
    fs.create("/f")
    fs.write("/f", bytes([7]) * BLOCK_SIZE, 0)
    ino = fs.stat("/f")
    return fs, off, ino.extents, ino.mtime


def test_submit_spellings_agree():
    fs, off, ext, mtime = _offload_plane()
    spec = {"task": "sum", "args": (ext[0].block, 1),
            "read_extents": ext, "mtime": mtime}
    r1 = off.submit(dict(spec))
    r2 = off.submit([dict(spec)])[0]
    r3 = off.submit(dict(spec), async_=True).result(timeout=30)
    # the positional form is the pre-spec spelling of the same entry point
    r4 = off.submit("sum", ext[0].block, 1, read_extents=ext, mtime=mtime)
    assert r1 == (7 * BLOCK_SIZE % 65536, "storage0")
    assert r1 == r2 == r3 == r4
    wait_no_leases(fs)
