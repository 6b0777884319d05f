"""Concurrency/stress coverage for the sharded multi-target offload plane:
threads × initiators × shards, admission rejection, backpressure, cache
pinning bounds, and load-balance tolerance."""
import threading
import time

import pytest

from repro.core import (
    AcceptAll, BLOCK_SIZE, BlockDevice, CPUThreshold, OffloadFS, RpcFabric,
    TokenRing,
)
from repro.core.engine import OffloadEngine
from repro.core.lsm import DBConfig, OffloadDB
from repro.core.lsm import compaction as C
from repro.core.offloader import TaskOffloader, serve_engine


def peek(io, blk, n=1):
    return io.offload_read(blk, n)[:4]


def build_plane(n_targets=2, *, policies=None, node="init0",
                lb_policy="least_outstanding", cache_blocks=256,
                max_inflight=4, blocks=1 << 16, shards=1):
    dev = BlockDevice(num_blocks=blocks)
    fs = OffloadFS(dev, node=node, shards=shards)
    fabric = RpcFabric()
    if policies is None:
        policies = [AcceptAll() for _ in range(n_targets)]
    engines = []
    for t in range(n_targets):
        eng = OffloadEngine(fs, node=f"storage{t}", cache_blocks=cache_blocks,
                            max_inflight=max_inflight)
        eng.register_stub("compact", C.stub_compact)
        eng.register_stub("log_recycle", C.stub_log_recycle)
        eng.register_stub("peek", peek)
        serve_engine(eng, fabric, policies[t])
        engines.append(eng)
    off = TaskOffloader(fs, fabric, node=node,
                        targets=[e.node for e in engines], lb_policy=lb_policy)
    off.register_local_stub("compact", C.stub_compact)
    off.register_local_stub("log_recycle", C.stub_log_recycle)
    off.register_local_stub("peek", peek)
    return dev, fs, fabric, engines, off


def run_threads(fns):
    errors = []

    def wrap(fn):
        try:
            fn()
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    ts = [threading.Thread(target=wrap, args=(fn,)) for fn in fns]
    for t in ts:
        t.start()
    for t in ts:
        t.join(60)
    assert not errors, errors[0]


# ------------------------------------------------------- balance + safety
def test_least_outstanding_balances_within_tolerance():
    _, fs, fabric, engines, off = build_plane(3)
    fs.create("/d")
    fs.write("/d", b"q" * BLOCK_SIZE * 8, 0)
    ex = fs.stat("/d").extents
    n_threads, per_thread = 6, 16

    def worker():
        for _ in range(per_thread):
            res, where = off.submit("peek", ex[0].block, read_extents=ex)
            assert res == b"qqqq" and where.startswith("storage")

    run_threads([worker] * n_threads)
    total = n_threads * per_thread
    assert off.stats.submitted == total
    assert off.stats.offloaded == total  # AcceptAll: nothing lost, none local
    assert sum(off.stats.by_target.values()) == total
    counts = [off.stats.by_target.get(e.node, 0) for e in engines]
    assert min(counts) > 0
    assert max(counts) <= 2 * min(counts)  # least-outstanding tolerance
    assert sum(e.tasks_run for e in engines) == total
    fabric.drain()
    assert fabric.total_subcalls() >= total


def test_no_lost_tasks_under_rejection_policies():
    """CPUThreshold (flapping) on shard0 + TokenRing (1 token) on shard1:
    every submission either offloads or falls back local — none lost."""
    flap = {"n": 0}

    def probe():
        flap["n"] += 1
        return 0.95 if flap["n"] % 3 else 0.1  # mostly overloaded

    policies = [CPUThreshold(probe, 0.8), TokenRing(1, ttl=0.05)]
    _, fs, fabric, engines, off = build_plane(
        2, policies=policies, lb_policy="admission_aware"
    )
    fs.create("/d")
    fs.write("/d", b"z" * BLOCK_SIZE * 4, 0)
    ex = fs.stat("/d").extents
    results = []
    lock = threading.Lock()

    def worker():
        for _ in range(20):
            res, where = off.submit("peek", ex[0].block, read_extents=ex)
            with lock:
                results.append((res, where))

    run_threads([worker] * 5)
    assert len(results) == 100
    assert all(r == b"zzzz" for r, _ in results)  # correct wherever it ran
    s = off.stats
    assert s.submitted == 100
    assert s.offloaded + s.ran_local == 100  # no lost tasks
    assert s.rejected == s.ran_local
    assert s.rejected > 0  # the policies actually pushed back
    assert sum(s.by_target.values()) == s.offloaded


# ----------------------------------------------------------- backpressure
def test_engine_backpressure_bounds_inflight_and_pins():
    barrier = threading.Barrier(4, timeout=30)

    def slow_peek(io, blk, n=1):
        data = io.offload_read(blk, n)
        try:
            barrier.wait(timeout=5)
        except threading.BrokenBarrierError:
            pass
        return data[:4]

    _, fs, fabric, engines, off = build_plane(
        1, max_inflight=3, cache_blocks=64
    )
    engines[0].register_stub("slow_peek", slow_peek)
    off.register_local_stub("slow_peek", slow_peek)
    fs.create("/d")
    fs.write("/d", b"p" * BLOCK_SIZE * 16, 0)
    ex = fs.stat("/d").extents

    def worker(i):
        def go():
            res, _ = off.submit("slow_peek", ex[0].block + i % 16,
                                read_extents=ex)
            assert res == b"pppp"
        return go

    run_threads([worker(i) for i in range(8)])
    q = engines[0].queue
    assert q.completed == 8
    assert q.inflight == 0
    assert q.inflight_peak <= 3  # bounded work queue held
    assert q.stalls > 0  # backpressure engaged
    assert engines[0].cache.stats.pinned_peak <= 64  # pins never exceed cap


# ------------------------------------------- DB: flush+compaction sharded
def test_db_flush_and_compaction_concurrent_across_two_engines():
    _, fs, fabric, engines, off = build_plane(2, blocks=1 << 17)
    cfg = DBConfig(memtable_bytes=8 * 1024, sstable_target_bytes=32 * 1024,
                   base_level_bytes=64 * 1024, l0_trigger=6)
    db = OffloadDB(fs, off, cfg)
    model = {}
    for i in range(5000):
        k = f"key{i % 700:06d}".encode()
        v = f"val{i:08d}".encode() * 5
        db.put(k, v)
        model[k] = v
        if i == 2500:
            db.flush_all()
    db.flush_all()
    # zero LeaseViolation (any would have raised through the futures), both
    # shards did real flush/compaction work, batched rounds happened
    assert db.stats["flushes"] > 0 and db.stats["compactions"] > 0
    assert all(e.tasks_run > 0 for e in engines)
    assert off.stats.batches > 0
    assert off.stats.offloaded == off.stats.submitted
    for e in engines:
        assert e.cache.stats.pinned_peak <= 256
    for k, v in model.items():
        assert db.get(k) == v, k


def test_failed_flush_round_keeps_data_and_reclaims_outputs():
    """A shard failing mid-round must not lose the immutable-memtable
    backlog or leak preallocated outputs; a retry after the shard heals
    flushes everything."""
    _, fs, fabric, engines, off = build_plane(2, blocks=1 << 17)
    sick = engines[1]
    healthy_stub = sick._stubs["log_recycle"]

    def broken(io, *a, **kw):
        raise RuntimeError("shard down")

    sick.register_stub("log_recycle", broken)
    cfg = DBConfig(memtable_bytes=4 * 1024, l0_trigger=99,  # no compaction
                   sstable_target_bytes=16 * 1024)
    db = OffloadDB(fs, off, cfg)
    model = {}
    for i in range(700):  # several sealed memtables
        k = f"k{i:05d}".encode()
        db.put(k, b"v" * 40)
        model[k] = b"v" * 40
    # flush_all seals the live memtable first, then flushes the backlog
    n_imm = len(db.imm) + (1 if len(db.mem) else 0)
    assert n_imm >= 2
    with pytest.raises(RuntimeError, match="shard down"):
        db.flush_all()
    # nothing lost: the un-flushed backlog is still readable...
    assert len(db.imm) == n_imm
    for k in (b"k00000", b"k00350", b"k00699"):
        assert db.get(k) == model[k]
    # ...and the aborted round's preallocated outputs were reclaimed
    assert fs.listdir("/sst/tmp-") == []
    # shard heals → retry flushes the whole backlog
    sick.register_stub("log_recycle", healthy_stub)
    db.flush_all()
    assert db.imm == [] and len(db.levels[0]) == n_imm
    for k, v in model.items():
        assert db.get(k) == v


# ------------------------------------------- a failing target's share
def slow_fill(io, block, nblocks, byte):
    time.sleep(0.2)  # settles well after the other target has failed
    io.offload_write(block, bytes([byte]) * (nblocks * BLOCK_SIZE))
    return nblocks


def broken_fill(io, *a, **kw):
    raise RuntimeError("target down")


def make_file(fs, path):
    fs.create(path)
    fs.write(path, bytes(BLOCK_SIZE), 0)
    return fs.stat(path).extents


def fill_spec(ext, target):
    return {"task": "fill", "args": (ext[0].block, 1, 0x5A),
            "write_extents": ext, "target": target}


def test_batch_with_a_failing_target_raises_after_every_share_settles():
    """``submit(specs)`` is a barrier: when one target's share fails the
    call raises that error, but only after the healthy target's share has
    run, and no lease or in-flight count outlives the call."""
    _, fs, _, engines, off = build_plane(2)
    engines[0].register_stub("fill", slow_fill)
    engines[1].register_stub("fill", broken_fill)
    a, b = make_file(fs, "/a"), make_file(fs, "/b")
    with pytest.raises(RuntimeError, match="target down"):
        off.submit([fill_spec(b, "storage1"), fill_spec(a, "storage0")])
    assert not fs._leases
    assert fs.lease_journal.replay() == {}
    assert set(off._outstanding.values()) == {0}
    assert fs.read("/a") == bytes([0x5A]) * BLOCK_SIZE  # healthy share ran
    assert fs.read("/b") == bytes(BLOCK_SIZE)


def test_async_spec_against_a_failing_target_resolves_with_its_error():
    """``submit(spec, async_=True)`` returns one future; a failing target
    resolves it with the target's exception and releases its lease."""
    _, fs, _, engines, off = build_plane(1)
    engines[0].register_stub("fill", broken_fill)
    ext = make_file(fs, "/a")
    fut = off.submit(fill_spec(ext, "storage0"), async_=True)
    assert isinstance(fut.exception(timeout=30), RuntimeError)
    with pytest.raises(RuntimeError, match="target down"):
        fut.result(timeout=30)
    assert not fs._leases
    assert fs.lease_journal.replay() == {}
    assert set(off._outstanding.values()) == {0}
    assert fs.read("/a") == bytes(BLOCK_SIZE)


# --------------------------------------------- striped placement routing
def test_placement_affinity_routes_to_owning_shard():
    """A task whose extents live on stripe k must land on targets[k]."""
    _, fs, fabric, engines, off = build_plane(
        3, shards=3, lb_policy="placement_affinity"
    )
    for shard in range(3):
        p = f"/f{shard}"
        fs.create(p, shard=shard)
        fs.write(p, bytes([65 + shard]) * BLOCK_SIZE * 4, 0)
        ex = fs.stat(p).extents
        assert all(e.shard == shard for e in ex)  # placement honoured
        res, where = off.submit("peek", ex[0].block, read_extents=ex)
        assert res == bytes([65 + shard]) * 4
        assert where == f"storage{shard}"  # routed to the owning shard
    assert off.stats.affinity_routed == 3
    assert fs.file_shard("/f0") == 0  # pinned placement query agrees
    # extent-less tasks take the least-outstanding FALLBACK (no affinity)
    for e in engines:
        e.register_stub("noop", lambda io: 7)
    res, where = off.submit("noop")
    assert res == 7
    assert where.startswith("storage")
    assert off.stats.affinity_routed == 3  # fallback did not count as affinity


def test_compaction_lands_on_shard_owning_its_extents():
    """A pinned tenant's flush AND compaction tasks all run on the engine
    owning its stripe; the other engine never sees its I/O."""
    _, fs, fabric, engines, off = build_plane(
        2, shards=2, lb_policy="placement_affinity", blocks=1 << 17
    )
    cfg = DBConfig(memtable_bytes=4 * 1024, sstable_target_bytes=16 * 1024,
                   base_level_bytes=48 * 1024, l0_trigger=3,
                   namespace="/a", placement_shard=1)
    db = OffloadDB(fs, off, cfg)
    for i in range(3000):
        db.put(f"k{i % 400:05d}".encode(), b"v" * 40)
    db.flush_all()
    assert db.stats["flushes"] > 0 and db.stats["compactions"] > 0
    assert off.stats.offloaded > 0
    assert engines[1].tasks_run == off.stats.offloaded  # all on shard 1
    assert engines[0].tasks_run == 0  # the co-tenant engine stays cold
    assert off.stats.affinity_routed == off.stats.submitted
    # every file the tenant owns sits on its pinned stripe (no spills)
    for p in fs.listdir("/a/"):
        for e in fs.stat(p).extents:
            assert fs.extmgr.shard_of(e.block) == 1
    assert fs.extmgr.spills == 0


def test_striped_wal_segments_ship_to_owning_shard():
    """Async WAL shipping on a striped volume: sealed segments land on the
    target whose stripe owns the WAL's blocks (not round-robin)."""
    _, fs, fabric, engines, off = build_plane(
        2, shards=2, lb_policy="placement_affinity", blocks=1 << 17
    )
    cfg = DBConfig(memtable_bytes=1 << 20, async_wal=True,
                   wal_segment_bytes=4 * BLOCK_SIZE,
                   namespace="/w", placement_shard=0)
    db = OffloadDB(fs, off, cfg)
    for i in range(2000):
        db.put(f"k{i:06d}".encode(), b"v" * 64)
    db.wal.wait_durable()
    fabric.drain()
    assert engines[0].wal_segments > 0
    assert engines[1].wal_segments == 0  # pinned: never the other shard
    assert db.get(b"k000000") == b"v" * 64


def test_striped_mount_preserves_placement():
    """Superblock round-trip: shard count, per-file pins and per-extent
    shard ids all survive flush_metadata + mount."""
    dev, fs, fabric, engines, off = build_plane(2, shards=2)
    fs.create("/pin", shard=1)
    fs.write("/pin", b"m" * BLOCK_SIZE * 3, 0)
    fs.flush_metadata()
    fs2 = OffloadFS.mount(dev, node="init0")
    assert fs2.shards == 2
    ino = fs2.stat("/pin")
    assert ino.shard == 1
    assert fs2.file_shard("/pin") == 1  # placement query survives mount
    assert all(e.shard == 1 and fs2.extmgr.shard_of(e.block) == 1
               for e in ino.extents)
    # new allocations still honour the pin after re-mount
    fs2.fallocate("/pin", BLOCK_SIZE * 8)
    assert all(e.shard == 1 for e in fs2.stat("/pin").extents)
    assert fs2.read("/pin", 0, 4) == b"mmmm"


# ---------------------------------------- M initiators × N threads stress
def test_multi_initiator_stress_shared_admission():
    """3 initiators (own volume each) × threads, sharing the two storage
    shards' admission policies — cross-initiator contention with zero
    LeaseViolations and zero lost tasks."""
    shared = [TokenRing(3, ttl=0.05), CPUThreshold(lambda: 0.5, 0.8)]
    planes = [
        build_plane(2, policies=shared, node=f"init{m}",
                    lb_policy="least_outstanding")
        for m in range(3)
    ]

    def initiator_job(m):
        dev, fs, fabric, engines, off = planes[m]

        def db_thread():
            cfg = DBConfig(memtable_bytes=4 * 1024, l0_trigger=3,
                           sstable_target_bytes=16 * 1024,
                           base_level_bytes=48 * 1024)
            db = OffloadDB(fs, off, cfg)
            for i in range(1200):
                db.put(f"i{m}k{i % 300:05d}".encode(), b"v" * 48)
            db.flush_all()
            assert db.get(f"i{m}k00000".encode()) is not None

        def peek_thread():
            fs_lock.acquire()
            try:
                if not fs.exists(f"/probe{m}"):
                    fs.create(f"/probe{m}")
                    fs.write(f"/probe{m}", b"s" * BLOCK_SIZE * 2, 0)
            finally:
                fs_lock.release()
            ino = fs.stat(f"/probe{m}")
            ex, mt = ino.extents, ino.mtime
            for _ in range(15):
                # mtime rides along: probe blocks may have been recycled
                # from deleted DB files the engine cache still remembers —
                # coarse mtime coherence bypasses those stale entries
                res, _ = off.submit("peek", ex[0].block,
                                    read_extents=ex, mtime=mt)
                assert res == b"ssss"

        fs_lock = threading.Lock()
        run_threads([db_thread] + [peek_thread] * 2)
        s = off.stats
        assert s.offloaded + s.ran_local == s.submitted  # nothing lost
        assert sum(s.by_target.values()) == s.offloaded

    run_threads([lambda m=m: initiator_job(m) for m in range(3)])
    # the shared ring never over-issued across ALL initiators
    assert len(shared[0].holders()) <= 3
