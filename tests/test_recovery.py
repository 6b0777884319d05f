"""Crash/re-mount recovery: the lease journal + the async WAL durability
watermark. This file also runs in isolation in CI (`recovery-smoke`, with
``-p no:cacheprovider``) so journal replay is exercised on a cold process.
"""
import threading
import time

import pytest

from repro.core import AcceptAll, BLOCK_SIZE, BlockDevice, OffloadFS, RpcFabric
from repro.core.engine import OffloadEngine
from repro.core.fs import (
    SB_JOURNAL_BLOCK, SB_JOURNAL_BLOCKS, LeaseViolation, _JHDR,
)
from repro.core.lsm import DBConfig, OffloadDB
from repro.core.lsm import compaction as C
from repro.core.lsm.wal import WalShipper, WriteAheadLog
from repro.core.offloader import TaskOffloader, serve_engine
from repro.sim.cluster import TESTBED, Cluster
from repro.sim.des import Sim


def make_fs(blocks=1 << 16):
    dev = BlockDevice(num_blocks=blocks)
    return dev, OffloadFS(dev, node="init0")


def build_plane(fs, n_targets=2, prefix="storage"):
    fabric = RpcFabric()
    engines = []
    for t in range(n_targets):
        eng = OffloadEngine(fs, node=f"{prefix}{t}", cache_blocks=512)
        eng.register_stub("compact", C.stub_compact)
        eng.register_stub("log_recycle", C.stub_log_recycle)
        serve_engine(eng, fabric, AcceptAll())
        engines.append(eng)
    off = TaskOffloader(fs, fabric, node="init0",
                        targets=[e.node for e in engines])
    return fabric, engines, off


# ---------------------------------------------------------- lease journal
def test_orphan_write_leases_survive_crash_and_remount():
    dev, fs = make_fs()
    fs.create("/a")
    fs.write("/a", b"x" * BLOCK_SIZE * 8, 0)
    fs.create("/b")
    fs.write("/b", b"y" * BLOCK_SIZE * 4, 0)
    # reprolint: allow[lease-raw] deliberate orphan grants: journal replay + fencing under test
    la = fs.grant_lease([], fs.stat("/a").extents)
    # reprolint: allow[lease-raw] deliberate orphan grants: journal replay + fencing under test
    fs.grant_lease([], fs.stat("/b").extents)
    # reprolint: allow[lease-raw] deliberate orphan grants: journal replay + fencing under test
    released = fs.grant_lease([], fs.stat("/a").extents[:0] or [])
    fs.release_lease(released)
    # reprolint: allow[lease-raw] deliberate orphan grants: journal replay + fencing under test
    ro = fs.grant_lease(fs.stat("/b").extents, [])  # read-only: not journaled
    fs.flush_metadata()
    del ro
    # CRASH: fs object dropped without releasing la/lb
    fs2 = OffloadFS.mount(dev, node="init0")
    orphans = fs2.orphan_leases()
    assert len(orphans) == 2  # both write leases, not the read-only one
    assert {o.task_id for o in orphans} == {la.task_id, la.task_id + 1}
    # quiesce discipline still holds until the orphans are fenced
    with pytest.raises(LeaseViolation):
        fs2.write("/a", b"z" * BLOCK_SIZE, 0)
    with pytest.raises(LeaseViolation):
        fs2.read("/a")
    reclaimed = fs2.reclaim_orphans()
    assert len(reclaimed) == len(orphans) == 2  # 100% of journaled orphans
    fs2.write("/a", b"z" * BLOCK_SIZE, 0)  # fenced: writable again
    assert fs2.read("/a", 0, 1) == b"z"
    assert not fs2.orphan_leases()
    # a third incarnation sees a clean journal
    fs3 = OffloadFS.mount(dev, node="init0")
    assert not fs3.orphan_leases()


def test_clean_release_leaves_no_orphans():
    dev, fs = make_fs()
    fs.create("/a")
    fs.write("/a", b"x" * BLOCK_SIZE * 4, 0)
    for _ in range(100):  # journal appends + wrap-free reuse
        # reprolint: allow[lease-raw] deliberate orphan grants: journal replay + fencing under test
        lease = fs.grant_lease([], fs.stat("/a").extents)
        fs.release_lease(lease)
    fs.flush_metadata()
    fs2 = OffloadFS.mount(dev, node="init0")
    assert fs2.orphan_leases() == []
    # task ids keep monotonically increasing across the re-mount
    # reprolint: allow[lease-raw] deliberate orphan grants: journal replay + fencing under test
    nxt = fs2.grant_lease([], fs2.stat("/a").extents)
    assert nxt.task_id > lease.task_id


def test_torn_journal_tail_drops_only_uncommitted_record():
    dev, fs = make_fs()
    leases = []
    for name in ("/a", "/b", "/c"):
        fs.create(name)
        fs.write(name, b"x" * BLOCK_SIZE * 2, 0)
        # reprolint: allow[lease-raw] deliberate orphan grants: journal replay + fencing under test
        leases.append(fs.grant_lease([], fs.stat(name).extents))
    fs.flush_metadata()
    # torn tail: truncate the LAST journal record mid-payload on the device
    raw = dev.read_blocks(SB_JOURNAL_BLOCK, SB_JOURNAL_BLOCKS, node="init0")
    off, last_off = 0, None
    while off + _JHDR.size <= len(raw):
        ln, _crc = _JHDR.unpack_from(raw, off)
        if ln == 0:
            break
        last_off = off
        off += _JHDR.size + ln
    assert last_off is not None
    torn = bytearray(raw[: last_off + _JHDR.size + 2])  # cut mid-record
    dev.write_blocks(SB_JOURNAL_BLOCK,
                     bytes(torn).ljust(SB_JOURNAL_BLOCKS * BLOCK_SIZE, b"\x00"),
                     node="init0")

    fs2 = OffloadFS.mount(dev, node="init0")
    assert fs2.lease_journal.torn_records == 1
    got = {o.task_id for o in fs2.orphan_leases()}
    # every committed grant recovered; the torn (uncommitted) one dropped
    want = {lease.task_id for lease in leases[:-1]}
    assert got == want
    assert len(fs2.reclaim_orphans()) == len(want) == 2
    # the torn grant's blocks are NOT quiesced (its record never committed)
    fs2.write("/c", b"w" * BLOCK_SIZE, 0)


def test_journal_compaction_keeps_outstanding_grants():
    dev, fs = make_fs()
    fs.create("/a")
    fs.write("/a", b"x" * BLOCK_SIZE * 2, 0)
    # reprolint: allow[lease-raw] deliberate orphan grants: journal replay + fencing under test
    keep = fs.grant_lease([], fs.stat("/a").extents)
    # churn far past the journal capacity: compaction must kick in
    fs.create("/b")
    fs.write("/b", b"y" * BLOCK_SIZE * 2, 0)
    for _ in range(8000):
        # reprolint: allow[lease-raw] deliberate orphan grants: journal replay + fencing under test
        lease = fs.grant_lease([], fs.stat("/b").extents)
        fs.release_lease(lease)
    assert fs.lease_journal.compactions >= 1
    fs.flush_metadata()
    fs2 = OffloadFS.mount(dev, node="init0")
    assert {o.task_id for o in fs2.orphan_leases()} == {keep.task_id}


# ------------------------------------------------------- async WAL plane
def test_wal_empty_flush_is_noop():
    _, fs = make_fs()
    wal = WriteAheadLog(fs, "/wal/t")
    wal.flush()
    wal.flush()
    assert wal.flushes == 0  # empty flushes must not count (Fig. 10 honesty)
    wal.append(b"k", b"v")
    wal.flush()
    wal.flush()  # buffer empty again
    assert wal.flushes == 1


def test_watermark_is_completion_ordered():
    dev, fs = make_fs()
    fabric, engines, off = build_plane(fs, 2)
    gate = threading.Event()
    inner0 = fabric._handlers[("storage0", "wal_append")]

    def gated(lease_wire, runs, payload):
        gate.wait(10.0)
        return inner0(lease_wire, runs, payload)

    fabric.register("storage0", "wal_append", gated)
    sh = WalShipper(fs, fabric, ["storage0", "storage1"], node="init0")
    wal = WriteAheadLog(fs, "/wal/x", shipper=sh, segment_bytes=2 * BLOCK_SIZE)
    # segment 1 → storage0 (gated), segment 2 → storage1 (completes first)
    while wal.segments < 2:
        wal.append(b"key%d" % wal.size, b"v" * 256)
    for _ in range(2000):  # let segment 2 land on the ungated shard
        if engines[1].wal_segments == 1:
            break
        time.sleep(0.001)
    assert engines[1].wal_segments == 1
    assert wal.durable_lsn == 0  # seg 2 done ≠ durable: seg 1 still in flight
    gate.set()
    wm = wal.wait_durable()
    assert wm == wal.size == wal.durable_lsn
    recs = list(wal.replay())
    assert len(recs) > 0
    fabric.drain()
    assert fs._leased_blocks == {}  # every segment lease released


def test_sync_wal_awaits_watermark():
    dev, fs = make_fs()
    fabric, engines, off = build_plane(fs, 2)
    sh = WalShipper(fs, fabric, [e.node for e in engines], node="init0")
    wal = WriteAheadLog(fs, "/wal/s", sync=True, shipper=sh)
    for i in range(25):
        wal.append(b"k%03d" % i, b"w" * 100)
        assert wal.durable_lsn == wal.size  # every append awaited durability
    assert len(list(wal.replay())) == 25


def test_db_crash_remount_recovers_durable_prefix_and_reclaims_orphans():
    dev, fs = make_fs(1 << 17)
    fabric, engines, off = build_plane(fs, 2)
    cfg = DBConfig(memtable_bytes=16 * 1024, sstable_target_bytes=32 * 1024,
                   l0_trigger=4, async_wal=True,
                   wal_segment_bytes=2 * BLOCK_SIZE)
    db = OffloadDB(fs, off, cfg)
    expected = {}
    for i in range(1500):
        k = b"key%06d" % (i % 300)
        v = b"val%08d" % i * 3
        db.put(k, v)
        expected[k] = v
    db.wal.wait_durable()
    fs.flush_metadata()
    # crash with an un-released batched job's write lease outstanding
    fs.create("/pending-output")
    fs.fallocate("/pending-output", 32 * 1024)
    # reprolint: allow[lease-raw] deliberate orphan grants: journal replay + fencing under test
    orphan = fs.grant_lease((), fs.stat("/pending-output").extents)
    fabric.drain()

    fs2 = OffloadFS.mount(dev, node="init0")
    assert [o.task_id for o in fs2.orphan_leases()] == [orphan.task_id]
    fabric2, engines2, off2 = build_plane(fs2, 2)
    db2 = OffloadDB.recover(fs2, off2, cfg)
    assert db2.orphans_reclaimed == [orphan.task_id]  # 100% reclaimed
    assert fs2.orphan_leases() == []
    for k, v in expected.items():
        assert db2.get(k) == v
    # the recovered db keeps ingesting on the async plane
    for i in range(200):
        db2.put(b"post%04d" % i, b"p" * 64)
    db2.flush_all()
    assert db2.get(b"post0000") == b"p" * 64
    assert db2.get(next(iter(expected))) == expected[next(iter(expected))]


def test_reopen_drops_torn_wal_tail():
    dev, fs = make_fs()
    wal = WriteAheadLog(fs, "/wal/z")
    offs = [wal.append(b"k%02d" % i, b"v" * 50) for i in range(10)]
    wal.flush()
    # torn tail: append more but "crash" before the flush lands fully —
    # simulate by writing garbage into the tail block past the flushed end
    ino = fs.stat("/wal/z")
    intact_end = wal.size
    fs.write("/wal/z", b"\xff" * BLOCK_SIZE, (intact_end // BLOCK_SIZE + 1) * BLOCK_SIZE)
    wal2, records = WriteAheadLog.reopen(fs, "/wal/z")
    assert len(records) == 10
    assert wal2.size == intact_end  # appends resume after the intact prefix
    assert offs[-1] < intact_end
    wal2.append(b"new", b"rec")
    wal2.flush()
    assert len(list(wal2.replay())) == 11


def test_reopen_ignores_stale_bytes_in_reused_blocks():
    """A crashed WAL whose fallocated tail reuses blocks freed by truncate
    must not replay the blocks' previous (record-encoded) content."""
    from repro.core.lsm.wal import encode_record

    dev, fs = make_fs()
    fs.create("/victim")
    stale = encode_record(b"stale-key", b"stale-val" * 100)
    fs.write("/victim", stale.ljust(2 * BLOCK_SIZE, b"\x00"), 0)
    fs.truncate("/victim", 0)  # blocks go back to the allocator
    # new WAL: one intact record, then allocate (but never write) the tail —
    # the async plane's prepare_write does exactly this before the crash
    wal = WriteAheadLog(fs, "/wal/reuse")
    wal.append(b"real", b"data")
    wal.flush()
    fs.prepare_write("/wal/reuse", BLOCK_SIZE, 2 * BLOCK_SIZE)
    wal2, records = WriteAheadLog.reopen(fs, "/wal/reuse")
    assert [k for k, _, _ in records] == [b"real"]


def test_fresh_mkfs_does_not_resurrect_previous_journal_generation():
    dev, fs1 = make_fs()
    fs1.create("/old")
    fs1.write("/old", b"o" * BLOCK_SIZE * 4, 0)
    # reprolint: allow[lease-raw] deliberate orphan grants: journal replay + fencing under test
    fs1.grant_lease([], fs1.stat("/old").extents)  # journaled, never released
    fs1.flush_metadata()
    # operator re-mkfs's the volume: new generation, NO write leases granted
    fs2 = OffloadFS(dev, node="init0")
    fs2.create("/new")
    fs2.write("/new", b"n" * BLOCK_SIZE * 4, 0)
    fs2.flush_metadata()
    # crash + mount: generation 1's journal must NOT quiesce /new's blocks
    fs3 = OffloadFS.mount(dev, node="init0")
    assert fs3.orphan_leases() == []
    assert fs3.read("/new") == b"n" * BLOCK_SIZE * 4


# ------------------------------------------------------------ DES model
def test_des_crash_remount_is_deterministic_and_metadata_only():
    def run(n_records):
        sim = Sim()
        cl = Cluster(sim, TESTBED)
        sim.spawn(cl.crash_remount(0, journal_records=n_records))
        return sim.run()

    t1, t2 = run(128), run(128)
    assert t1 == t2  # deterministic
    t_big = run(4096)
    assert t_big > t1  # replay cost scales with journal records…
    assert t_big < 0.05  # …but stays metadata-cheap (no data scanning)


def test_des_wal_ship_off_foreground_path():
    sim = Sim()
    cl = Cluster(sim, TESTBED, n_storage=2)
    sim.spawn(cl.wal_ship(0, 64 * 1024, target=1))
    t = sim.run()
    assert 0 < t < 1e-3  # one RTT + segment bytes, no posvol crossing
    assert cl.posvol_t[1].served == 0
    assert cl.nvme_w_t[1].served == 1
