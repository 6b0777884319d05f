"""OffloadFS core: extents, leases, authorization, coherence, mount."""
import pytest

from repro.core import BLOCK_SIZE, BlockDevice, OffloadFS, RpcFabric
from repro.core.engine import OffloadEngine
from repro.core.fs import LeaseViolation
from repro.core.offloader import TaskOffloader, serve_engine


def make_fs(blocks=4096):
    dev = BlockDevice(num_blocks=blocks)
    return dev, OffloadFS(dev, node="init0")


def test_create_write_read_roundtrip():
    _, fs = make_fs()
    fs.create("/a")
    data = bytes(range(256)) * 33  # unaligned length
    fs.write("/a", data, 0)
    assert fs.read("/a") == data
    assert fs.read("/a", 100, 50) == data[100:150]
    fs.truncate("/a", 100)
    assert fs.read("/a") == data[:100]


def test_delete_frees_blocks():
    _, fs = make_fs()
    free0 = fs.extmgr.free_blocks
    fs.create("/a")
    fs.write("/a", b"x" * (BLOCK_SIZE * 10), 0)
    assert fs.extmgr.free_blocks == free0 - 10
    fs.delete("/a")
    assert fs.extmgr.free_blocks == free0


def test_lease_blocks_initiator_writes():
    _, fs = make_fs()
    fs.create("/a")
    fs.write("/a", b"y" * BLOCK_SIZE * 4, 0)
    ex = fs.stat("/a").extents
    # reprolint: allow[lease-raw] exercises the raw grant/release lease protocol under test
    lease = fs.grant_lease([], ex)
    with pytest.raises(LeaseViolation):
        fs.write("/a", b"z" * BLOCK_SIZE, 0)
    with pytest.raises(LeaseViolation):
        fs.delete("/a")
    fs.release_lease(lease)
    fs.write("/a", b"z" * BLOCK_SIZE, 0)  # ok now


def test_truncate_refuses_leased_drop_blocks():
    """truncate frees+trims the dropped tail — like delete/rename it must
    fence BOTH lease kinds over exactly those blocks (PR 9 fix: the tail
    of a file a task was still writing could be recycled under it)."""
    _, fs = make_fs()
    fs.create("/a")
    fs.write("/a", b"t" * BLOCK_SIZE * 4, 0)
    tail = [e for e in fs.stat("/a").extents]
    # reprolint: allow[lease-raw] exercises the raw grant/release lease protocol under test
    wlease = fs.grant_lease([], tail)
    with pytest.raises(LeaseViolation):
        fs.truncate("/a", BLOCK_SIZE)  # dropped blocks are write-leased
    fs.release_lease(wlease)
    # reprolint: allow[lease-raw] exercises the raw grant/release lease protocol under test
    rlease = fs.grant_lease(tail, [])
    with pytest.raises(LeaseViolation):
        fs.truncate("/a", BLOCK_SIZE)  # dropped blocks are read-leased
    fs.release_lease(rlease)
    fs.truncate("/a", BLOCK_SIZE)  # unleased: proceeds
    assert fs.stat("/a").size == BLOCK_SIZE
    # truncating only the UNLEASED tail under a lease on the kept head is
    # fine: the fence covers exactly the dropped blocks
    fs.write("/a", b"h" * BLOCK_SIZE * 2, 0)
    head = [e for e in fs.stat("/a").extents if e.file_offset == 0][:1]
    # reprolint: allow[lease-raw] exercises the raw grant/release lease protocol under test
    hlease = fs.grant_lease([], head)
    fs.truncate("/a", BLOCK_SIZE)
    fs.release_lease(hlease)


def test_target_cannot_touch_unauthorized_blocks():
    dev, fs = make_fs()
    fs.create("/a")
    fs.write("/a", b"a" * BLOCK_SIZE * 2, 0)
    fs.create("/secret")
    fs.write("/secret", b"s" * BLOCK_SIZE, 0)
    ex = fs.stat("/a").extents
    sx = fs.stat("/secret").extents
    # reprolint: allow[lease-raw] exercises the raw grant/release lease protocol under test
    lease = fs.grant_lease(ex, [])
    eng = OffloadEngine(fs, node="storage0")

    def sneaky(io):
        return io.offload_read(sx[0].block, 1)

    eng.register_stub("sneaky", sneaky)
    with pytest.raises(LeaseViolation):
        eng.run_task("sneaky", lease)

    def sneaky_write(io):
        io.offload_write(ex[0].block, b"w" * BLOCK_SIZE)  # read-only lease

    eng.register_stub("sneaky_write", sneaky_write)
    with pytest.raises(LeaseViolation):
        eng.run_task("sneaky_write", lease)


def test_mtime_coherence_bypasses_stale_cache():
    dev, fs = make_fs()
    fs.create("/a")
    fs.write("/a", b"1" * BLOCK_SIZE, 0)
    eng = OffloadEngine(fs, node="storage0", cache_blocks=64)
    eng.register_stub("read", lambda io, blk: io.offload_read(blk, 1))
    ex = fs.stat("/a").extents

    # reprolint: allow[lease-raw] exercises the raw grant/release lease protocol under test
    lease = fs.grant_lease(ex, [])
    t1 = fs.stat("/a").mtime
    r1 = eng.run_task("read", lease, ex[0].block, mtime=t1)
    fs.release_lease(lease)
    assert r1[:1] == b"1"
    # initiator writes directly → cached block is stale
    fs.write("/a", b"2" * BLOCK_SIZE, 0)
    # reprolint: allow[lease-raw] exercises the raw grant/release lease protocol under test
    lease = fs.grant_lease(ex, [])
    t2 = fs.stat("/a").mtime
    r2 = eng.run_task("read", lease, ex[0].block, mtime=t2)
    assert r2[:1] == b"2"  # coherence: bypassed the stale entry
    assert eng.cache.stats.bypasses >= 1


def test_superblock_mount_roundtrip():
    dev, fs = make_fs()
    fs.create("/x/a")
    fs.write("/x/a", b"q" * 5000, 0)
    fs.create("/x/b")
    fs.flush_metadata()
    fs2 = OffloadFS.mount(dev, node="init0")
    assert fs2.read("/x/a") == b"q" * 5000
    assert fs2.exists("/x/b")
    # allocator rebuilt: new allocations don't collide with existing data
    fs2.create("/x/c")
    fs2.write("/x/c", b"n" * BLOCK_SIZE * 8, 0)
    assert fs2.read("/x/a") == b"q" * 5000


def test_initiator_read_of_leased_write_blocks_raises():
    """Quiesce discipline: while a task holds a WRITE lease the initiator
    must not even READ those blocks (no DLM orders the access)."""
    _, fs = make_fs()
    fs.create("/a")
    fs.write("/a", b"y" * BLOCK_SIZE * 4, 0)
    fs.create("/other")
    fs.write("/other", b"o" * BLOCK_SIZE, 0)
    ex = fs.stat("/a").extents
    # reprolint: allow[lease-raw] exercises the raw grant/release lease protocol under test
    lease = fs.grant_lease([], ex)
    with pytest.raises(LeaseViolation):
        fs.read("/a")
    with pytest.raises(LeaseViolation):
        fs.read("/a", 0, 10)
    assert fs.read("/other") == b"o" * BLOCK_SIZE  # unleased files fine
    fs.release_lease(lease)
    assert fs.read("/a") == b"y" * BLOCK_SIZE * 4
    # READ leases do not quiesce the initiator (it only must not mutate)
    # reprolint: allow[lease-raw] exercises the raw grant/release lease protocol under test
    lease = fs.grant_lease(ex, [])
    assert fs.read("/a") == b"y" * BLOCK_SIZE * 4
    fs.release_lease(lease)


def test_double_release_is_idempotent():
    _, fs = make_fs()
    fs.create("/a")
    fs.write("/a", b"x" * BLOCK_SIZE * 2, 0)
    ex = fs.stat("/a").extents
    # reprolint: allow[lease-raw] exercises the raw grant/release lease protocol under test
    lease = fs.grant_lease([], ex)
    fs.release_lease(lease)
    fs.release_lease(lease)  # second release: no-op, no raise
    assert lease.done
    fs.write("/a", b"w" * BLOCK_SIZE, 0)  # blocks really free
    # a later lease over the same blocks is unaffected by the stale handle
    # reprolint: allow[lease-raw] exercises the raw grant/release lease protocol under test
    lease2 = fs.grant_lease([], ex)
    fs.release_lease(lease)  # releasing the OLD lease again: still no-op
    with pytest.raises(LeaseViolation):
        fs.write("/a", b"v" * BLOCK_SIZE, 0)  # lease2 still guards
    fs.release_lease(lease2)


def _lease_chunks(fs, n, nblocks=64):
    """Grant ``n`` write leases of ``nblocks`` each over blocks [0, n*nblocks),
    as a KV put's wave of chunk grants leaves them outstanding."""
    from repro.core import Extent

    # reprolint: allow[lease-raw] outstanding leases under test, dropped with the fs
    return [fs.grant_lease([], [Extent(0, i * nblocks, nblocks)])
            for i in range(n)]


@pytest.mark.parametrize("outstanding, release, reads, writes, refused", [
    pytest.param(1, False, [], [(32, 64)], [32, 33, 34, 35],
                 id="write-over-write-refused"),
    pytest.param(1024, False, [], [(1024 * 64, 64)], None,
                 id="disjoint-write-under-1024-leases"),
    pytest.param(1, False, [(0, 64)], [], None,
                 id="read-over-write-granted"),
    pytest.param(1, True, [], [(0, 64)], None,
                 id="regrant-after-release"),
    pytest.param(1024, False, [], [(1024 * 64, 8), (777, 2)], [777, 778],
                 id="partial-overlap-refusal-changes-nothing"),
])
def test_grant_lease_write_set_conflicts(outstanding, release, reads, writes,
                                         refused):
    """A write grant is refused exactly when its write set meets a block
    under an outstanding write lease; read sets are never checked; a refusal
    names the lowest overlapping blocks and leaves the maps and the journal
    as they were."""
    from repro.core import Extent

    _, fs = make_fs()
    held = _lease_chunks(fs, outstanding)
    if release:
        for lease in held:
            fs.release_lease(lease)
    before = (dict(fs._leased_blocks), dict(fs._leases),
              fs.lease_journal.appends)
    read_ex = [Extent(0, b, n) for b, n in reads]
    write_ex = [Extent(0, b, n) for b, n in writes]
    if refused is not None:
        with pytest.raises(LeaseViolation) as err:
            # reprolint: allow[lease-raw] refused grant under test, never held
            fs.grant_lease(read_ex, write_ex)
        assert str(refused) in str(err.value)
        assert (dict(fs._leased_blocks), dict(fs._leases),
                fs.lease_journal.appends) == before
        return
    # reprolint: allow[lease-raw] exercises the raw grant/release lease protocol under test
    lease = fs.grant_lease(read_ex, write_ex)
    wb = {b for b, n in writes for b in range(b, b + n)}
    assert lease.write_blocks == wb
    assert all(fs._leased_blocks[b] == lease.task_id for b in wb)
    assert fs.lease_journal.appends == before[2] + (1 if wb else 0)
    fs.release_lease(lease)
    assert dict(fs._leased_blocks) == before[0]


class _WalkCountingMap(dict):
    """A block map that counts each walk over its keys or entries."""

    def __init__(self, *args):
        super().__init__(*args)
        self.walks = 0

    def _walk(self, view):
        self.walks += 1
        return view

    def __iter__(self):
        return self._walk(super().__iter__())

    def keys(self):
        return self._walk(super().keys())

    def values(self):
        return self._walk(super().values())

    def items(self):
        return self._walk(super().items())


def test_grant_lease_never_walks_the_leased_block_map():
    """With a wave's worth of write leases outstanding, a grant probes the
    leased-block map per block of its own write set: it never walks the map,
    so a wave of grants stays linear in its chunks."""
    from repro.core import Extent

    _, fs = make_fs()
    _lease_chunks(fs, 1024)
    fs._leased_blocks = _WalkCountingMap(fs._leased_blocks)
    # reprolint: allow[lease-raw] exercises the raw grant/release lease protocol under test
    fresh = fs.grant_lease([], [Extent(0, 1024 * 64, 64)])
    with pytest.raises(LeaseViolation):
        # reprolint: allow[lease-raw] refused grant under test, never held
        fs.grant_lease([], [Extent(0, 100, 4)])
    # reprolint: allow[lease-raw] exercises the raw grant/release lease protocol under test
    reader = fs.grant_lease([Extent(0, 0, 64 * 1024)], [])
    fs.release_lease(reader)
    fs.release_lease(fresh)
    assert fs._leased_blocks.walks == 0
    assert len(fs._leased_blocks) == 1024 * 64


def test_stale_mtime_reads_bypass_offload_cache_counted():
    """Coarse mtime coherence: every cached block older than the request's
    mtime is bypassed (and re-read from NVMe), with exact accounting."""
    dev, fs = make_fs()
    fs.create("/a")
    fs.write("/a", b"1" * BLOCK_SIZE * 3, 0)
    eng = OffloadEngine(fs, node="storage0", cache_blocks=64)
    eng.register_stub("read", lambda io, blk, n: io.offload_read(blk, n))
    ex = fs.stat("/a").extents

    # reprolint: allow[lease-raw] exercises the raw grant/release lease protocol under test
    lease = fs.grant_lease(ex, [])
    t1 = fs.stat("/a").mtime
    eng.run_task("read", lease, ex[0].block, 3, mtime=t1)  # warm: 3 misses
    fs.release_lease(lease)
    assert eng.cache.stats.misses == 3 and eng.cache.stats.bypasses == 0
    # initiator overwrites → all 3 cached blocks are stale
    fs.write("/a", b"2" * BLOCK_SIZE * 3, 0)
    # reprolint: allow[lease-raw] exercises the raw grant/release lease protocol under test
    lease = fs.grant_lease(ex, [])
    t2 = fs.stat("/a").mtime
    r = eng.run_task("read", lease, ex[0].block, 3, mtime=t2)
    fs.release_lease(lease)
    assert r == b"2" * BLOCK_SIZE * 3  # fresh data, not the stale cache
    assert eng.cache.stats.bypasses == 3  # every stale block counted
    # re-read at same mtime now hits (cache was refreshed by the bypass)
    # reprolint: allow[lease-raw] exercises the raw grant/release lease protocol under test
    lease = fs.grant_lease(ex, [])
    eng.run_task("read", lease, ex[0].block, 3, mtime=t2)
    fs.release_lease(lease)
    assert eng.cache.stats.hits == 3
    assert eng.cache.stats.bypasses == 3  # unchanged


def test_rejected_offload_runs_locally():
    from repro.core.admission import RejectAll

    dev, fs = make_fs()
    fabric = RpcFabric()
    eng = OffloadEngine(fs, node="storage0")
    serve_engine(eng, fabric, RejectAll())
    off = TaskOffloader(fs, fabric, node="init0")
    fs.create("/a")
    fs.write("/a", b"z" * BLOCK_SIZE, 0)
    ex = fs.stat("/a").extents
    stub = lambda io, blk: io.offload_read(blk, 1)[:1]
    off.register_local_stub("peek", stub)
    eng.register_stub("peek", stub)
    res, where = off.submit("peek", ex[0].block, read_extents=ex)
    assert res == b"z" and where == "init0"
    assert off.stats.rejected == 1 and off.stats.ran_local == 1


# --------------------------------------------------- PR 4 regression fixes
def test_rename_over_existing_frees_destination():
    """rename() used to clobber silently: the destination inode and all its
    blocks leaked forever. It must free them like delete() does."""
    _, fs = make_fs()
    fs.create("/a")
    fs.write("/a", b"A" * BLOCK_SIZE * 2, 0)
    fs.create("/b")
    fs.write("/b", b"B" * BLOCK_SIZE * 3, 0)
    free_before = fs.extmgr.free_blocks
    n_inodes = len(fs.listdir())
    fs.rename("/a", "/b")
    assert fs.read("/b") == b"A" * BLOCK_SIZE * 2
    assert not fs.exists("/a")
    assert fs.extmgr.free_blocks == free_before + 3  # victim's blocks back
    assert len(fs.listdir()) == n_inodes - 1  # victim inode gone
    # freed blocks are trimmed: a later reader must not see stale bytes
    fs.create("/c")
    fs.write("/c", b"\x00" * BLOCK_SIZE * 3, 0)
    assert b"B" not in fs.read("/c")


def test_rename_over_leased_destination_raises():
    _, fs = make_fs()
    fs.create("/a")
    fs.write("/a", b"A" * BLOCK_SIZE, 0)
    fs.create("/b")
    fs.write("/b", b"B" * BLOCK_SIZE, 0)
    # reprolint: allow[lease-raw] exercises the raw grant/release lease protocol under test
    lease = fs.grant_lease([], fs.stat("/b").extents)
    with pytest.raises(LeaseViolation):
        fs.rename("/a", "/b")
    fs.release_lease(lease)
    assert fs.read("/a") == b"A" * BLOCK_SIZE  # untouched on refusal
    fs.rename("/a", "/b")  # fine after release


def test_rename_missing_source_and_self():
    _, fs = make_fs()
    with pytest.raises(FileNotFoundError):
        fs.rename("/nope", "/x")
    fs.create("/a")
    fs.write("/a", b"A" * BLOCK_SIZE, 0)
    free_before = fs.extmgr.free_blocks
    fs.rename("/a", "/a")  # rename to self: no-op, nothing freed
    assert fs.read("/a") == b"A" * BLOCK_SIZE
    assert fs.extmgr.free_blocks == free_before


def test_free_splits_runs_at_stripe_boundaries():
    """A run persisted under an older stripe layout can cross today's
    boundaries; free() must split it per stripe like carve() does, or the
    whole run lands in the stripe of its start block and corrupts
    per-shard accounting."""
    from repro.core import Extent, ExtentManager

    mgr = ExtentManager(4096, reserved=64, shards=4)
    full = {k: mgr.free_blocks_in(k) for k in range(4)}
    lo1, _ = mgr.stripe_range(1)
    # simulate an old-layout run straddling the stripe-0/1 boundary
    start, length = lo1 - 100, 250
    mgr.carve(start, length)
    assert mgr.free_blocks_in(0) == full[0] - 100
    assert mgr.free_blocks_in(1) == full[1] - 150
    mgr.free([Extent(0, start, length, 0)])
    for k in range(4):
        assert mgr.free_blocks_in(k) == full[k]
        assert mgr.fragmentation(k) == 1  # boundary pieces merged back


def test_spills_counted_only_when_foreign_blocks_taken():
    """`spills` must count allocations that actually TOOK blocks from a
    foreign stripe — not merely visited an exhausted one."""
    from repro.core import ExtentManager

    mgr = ExtentManager(128, reserved=0, shards=2)  # stripes [0,64) [64,128)
    mgr.alloc(10, shard=0)
    assert mgr.spills == 0  # fully served by the preferred stripe
    exts = mgr.alloc(60, shard=0)  # 54 left on stripe 0 → 6 spill to 1
    assert mgr.spills == 1
    assert {e.shard for e in exts} == {0, 1}
    mgr.alloc(58, shard=1)  # drains stripe 1 exactly: not a spill
    assert mgr.spills == 1
    with pytest.raises(IOError):
        mgr.alloc(10, shard=1)  # volume full: failed allocs never count
    assert mgr.spills == 1


def test_restripe_mount_preserves_content_and_accounting():
    """Mounting with an explicit shards= override re-stripes the volume:
    data survives, stale pins/shard-ids are re-derived, and freeing
    old-layout extents keeps per-stripe accounting exact."""
    dev, fs = make_fs(blocks=1 << 13)
    fs.create("/big")
    fs.write("/big", b"q" * (BLOCK_SIZE * 3000), 0)
    fs.create("/small")
    fs.write("/small", b"r" * (BLOCK_SIZE * 10), 0)
    fs.flush_metadata()
    fs2 = OffloadFS.mount(dev, node="init0", shards=4)
    assert fs2.shards == 4
    assert fs2.read("/big") == b"q" * (BLOCK_SIZE * 3000)
    assert fs2.read("/small") == b"r" * (BLOCK_SIZE * 10)
    # the 3000-block extent straddles several new stripes; deleting it must
    # split the free per stripe (the free() boundary fix)
    fs2.delete("/big")
    fs2.delete("/small")
    for k in range(4):
        lo, hi = fs2.extmgr.stripe_range(k)
        assert fs2.extmgr.free_blocks_in(k) == hi - lo
        assert fs2.extmgr.fragmentation(k) == 1
    # and the re-striped volume allocates per stripe as usual
    fs2.create("/new", shard=3)
    fs2.write("/new", b"n" * BLOCK_SIZE * 5, 0)
    assert fs2.file_shard("/new") == 3
