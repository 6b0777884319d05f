"""KV-cache offload serving plane (PR 7): KvCacheStore over OffloadFS.

  * put → fetch roundtrip, byte-exact, across all three wire planes
    (local scoped-lease, TaskOffloader stream, ClusterRouter)
  * prefix-aware placement: exact-match dedupe, prefix-family stripe
    inheritance, round-robin scattering as the counterfactual
  * ``serve.generate`` emits IDENTICAL tokens with an in-memory cache,
    a fetched-offloaded cache, and a warm store hit that skips prefill
  * crash fencing: a prefill initiator dies mid-store (warm in-process
    via ``ServingCrash`` and COLD-PROCESS via a real killed subprocess);
    takeover fences 100% of the orphans, survivors decode byte-exact
  * scoped lease context managers (``fs.write_lease``/``fs.read_lease``):
    release on error, survive simulated crashes

Run this file directly (``python tests/test_kv_serving.py --child <dir>``)
to execute the cold-process child: it stores one complete entry, dies
mid-store of a second with the write lease journaled but unreleased, and
leaves the device image for the parent (the CI ``serving-smoke`` step).
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import (  # noqa: E402
    BlockDevice,
    ClusterRouter,
    FaultyFabric,
    OffloadFS,
    TaskOffloader,
    standby_takeover,
)
from repro.core.admission import AcceptAll  # noqa: E402
from repro.core.engine import OffloadEngine  # noqa: E402
from repro.core.fs import LeaseViolation  # noqa: E402
from repro.core.offloader import serve_engine  # noqa: E402
from repro.serve.kvstore import (  # noqa: E402
    KvCacheStore,
    ServingCrash,
    attach_store,
    register_kv_stubs,
)


# ------------------------------------------------------------- harness
def small_cache(n=2048):
    return {"k": jnp.arange(n, dtype=jnp.float32),
            "v": jnp.arange(n, dtype=jnp.float32) * 0.5,
            "pos": jnp.array([7, 9], jnp.int32)}


def caches_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def build_plane(n_targets=3, *, shards=4, seed=0):
    dev = BlockDevice(num_blocks=1 << 16)
    fs = OffloadFS(dev, node="init0", shards=shards)
    fabric = FaultyFabric(seed=seed)
    engines = []
    for t in range(n_targets):
        eng = OffloadEngine(fs, node=f"storage{t}", enable_cache=False)
        register_kv_stubs(eng)
        serve_engine(eng, fabric, AcceptAll())
        engines.append(eng)
    off = TaskOffloader(fs, fabric, node="init0",
                        targets=[e.node for e in engines],
                        lb_policy="least_outstanding")
    return dev, fs, fabric, engines, off


def wait_no_leases(fs, timeout=5.0):
    deadline = time.time() + timeout
    while fs._leases and time.time() < deadline:
        time.sleep(0.002)
    assert not fs._leases


# ------------------------------------------------------- local plane
def test_put_fetch_roundtrip_local():
    dev = BlockDevice(num_blocks=1 << 15)
    fs = OffloadFS(dev, node="init0", shards=2)
    store = KvCacheStore(fs, chunk_blocks=2)  # forces multi-chunk blobs
    cache = small_cache()
    rec = store.put([1, 2, 3, 4], cache)
    assert not rec["deduped"] and rec["bytes"] > 0
    got = store.fetch([1, 2, 3, 4])
    assert caches_equal(cache, got)
    assert store.stats.put_chunks > 1  # chunking actually happened
    assert store.fetch([9, 9]) is None  # unknown prompt → recompute
    assert not fs._leases


def test_scoped_lease_context_managers():
    dev = BlockDevice(num_blocks=1 << 14)
    fs = OffloadFS(dev, node="init0")
    fs.create("/f")
    fs.write("/f", b"\xAB" * 8192, 0)
    # write_lease: grants, exposes runs, releases on normal exit
    with fs.write_lease("/f") as lease:
        assert lease.runs and fs._leases
        blk = lease.runs[0][0]
        fs.authorized_write(lease, blk, b"\xCD" * 4096, node=fs.node)
    assert not fs._leases
    with pytest.raises(LeaseViolation):
        fs.authorized_write(lease, blk, b"\xEE" * 4096, node=fs.node)
    # read_lease under plain failure: released, exception propagates
    with pytest.raises(RuntimeError):
        with fs.read_lease("/f") as lease:
            raise RuntimeError("reader failed")
    assert not fs._leases
    assert fs.read("/f")[:4096] == b"\xCD" * 4096
    # simulated crash (BaseException): the lease must SURVIVE and fence
    # the blocks until orphan reclaim
    with pytest.raises(ServingCrash):
        with fs.write_lease("/f"):
            raise ServingCrash("process died")
    assert len(fs._leases) == 1
    with pytest.raises(LeaseViolation):
        fs.read("/f")
    # only a takeover (journal replay) fences the crashed grant
    fs.flush_metadata()
    fs2, fenced = standby_takeover(dev)
    assert len(fenced) == 1 and not fs2._leases
    assert fs2.read("/f")[:4096] == b"\xCD" * 4096


# ---------------------------------------------------------- placement
def test_prefix_placement_dedupes_family_onto_one_stripe():
    dev = BlockDevice(num_blocks=1 << 15)
    fs = OffloadFS(dev, node="init0", shards=4)
    store = KvCacheStore(fs, placement="prefix", chunk_blocks=2)
    cache = small_cache(512)
    rec = store.put([5, 6, 7, 8], cache)
    # exact re-store: zero-I/O dedupe on the same stripe
    again = store.put([5, 6, 7, 8], cache)
    assert again["deduped"] and again["shard"] == rec["shard"]
    # a prefix extension inherits the family's stripe
    ext = store.put([5, 6, 7, 8, 9, 10], cache)
    assert not ext["deduped"] and ext["shard"] == rec["shard"]
    # an unrelated prompt may land anywhere, but its own family sticks
    other = store.put([100, 101], cache)
    assert store.put([100, 101, 102], cache)["shard"] == other["shard"]
    assert store.stats.dedupe_hits == 1


def test_round_robin_scatters_and_loses_dedupe():
    cache = small_cache(512)
    hits = {}
    for policy in ("prefix", "round_robin"):
        dev = BlockDevice(num_blocks=1 << 16)
        fs = OffloadFS(dev, node="init0", shards=4)
        store = KvCacheStore(fs, placement=policy, chunk_blocks=2)
        for _ in range(8):  # one hot prompt, eight sessions
            store.put([42, 43, 44], cache)
        hits[policy] = store.stats.dedupe_hits
    assert hits["prefix"] == 7  # every session after the first dedupes
    assert hits["round_robin"] < hits["prefix"]  # scattered re-stores


# --------------------------------------------------------- wire planes
def test_offloader_plane_roundtrip():
    dev, fs, fabric, engines, off = build_plane()
    store = KvCacheStore(fs, off=off, chunk_blocks=1)
    cache = small_cache()
    store.put([3, 1, 4, 1, 5], cache)
    got = store.fetch([3, 1, 4, 1, 5])
    assert caches_equal(cache, got)
    assert store.stats.fetch_chunks > 1
    wait_no_leases(fs)


def test_offloader_plane_cache_larger_than_lease_journal():
    """More chunks than the lease journal holds live grants (~2.8 Ki) and
    than the superblock holds uncompressed inodes: the put moves in waves
    and the compressed inode table still fits."""
    dev, fs, fabric, engines, off = build_plane()
    store = KvCacheStore(fs, off=off, chunk_blocks=1)
    cache = small_cache(1600 * 1024)  # 2 x 6.4 MB of f32 -> ~3200 chunks
    store.put([6, 0, 0, 0], cache)
    assert store.stats.put_chunks > 2 * store.WAVE_CHUNKS
    assert caches_equal(cache, store.fetch([6, 0, 0, 0]))
    wait_no_leases(fs)


def test_router_plane_roundtrip_and_midfetch_kill():
    dev, fs, fabric, engines, off = build_plane()
    router = ClusterRouter(off, max_probe_failures=2)
    store = KvCacheStore(fs, router=router, chunk_blocks=1)
    cache = small_cache()
    store.put([2, 7, 1, 8], cache)
    assert caches_equal(cache, store.fetch([2, 7, 1, 8]))
    wait_no_leases(fs)
    # every target dies mid-fetch: the error surfaces, nothing leaks
    for eng in engines:
        fabric.kill(eng.node)
    with pytest.raises(Exception):
        store.fetch([2, 7, 1, 8])
    wait_no_leases(fs)
    for eng in engines:
        fabric.revive(eng.node)
    assert caches_equal(cache, store.fetch([2, 7, 1, 8]))
    wait_no_leases(fs)


# ----------------------------------------------------------- generate
def test_generate_identical_tokens_in_memory_vs_offloaded():
    from repro.models.config import get_config
    from repro.models.model import build_model
    from repro.serve import generate

    cfg = get_config("qwen3-1.7b:smoke").with_(
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
        d_ff=128, vocab_size=256, head_dim=16)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (2, 10), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    ref = generate(model, params, prompt, steps=6, max_len=24)

    dev = BlockDevice(num_blocks=1 << 15)
    fs = OffloadFS(dev, node="init0", shards=2)
    store = KvCacheStore(fs)
    # cold: prefill → offload → decode from the FETCHED copy
    cold = generate(model, params, prompt, steps=6, max_len=24,
                    kv_store=store)
    assert np.array_equal(np.asarray(ref), np.asarray(cold))
    assert store.stats.puts == 1 and store.stats.fetches == 1
    # warm: the exact prompt is stored — prefill skipped entirely
    warm = generate(model, params, prompt, steps=6, max_len=24,
                    kv_store=store)
    assert np.array_equal(np.asarray(ref), np.asarray(warm))
    assert store.stats.puts == 1 and store.stats.fetches == 2
    assert not fs._leases


# ------------------------------------------------------ crash fencing
def test_mid_put_crash_then_takeover_fences_and_serves():
    dev = BlockDevice(num_blocks=1 << 15)
    fs = OffloadFS(dev, node="init0", shards=2)
    store = KvCacheStore(fs, chunk_blocks=2)
    cache = small_cache()
    store.put([1, 2, 3], cache)
    with pytest.raises(ServingCrash):
        store.put([6, 6, 6], cache, failpoint="mid_put")
    assert len(fs._leases) == 1  # the orphan the crash left behind

    fs2, fenced = standby_takeover(dev, shards=2)
    assert len(fenced) == 1 and not fs2._leases
    store2 = attach_store(fs2, chunk_blocks=2)
    assert caches_equal(cache, store2.fetch([1, 2, 3]))
    assert not store2.contains([6, 6, 6])  # half-store never committed


def test_catalog_attach_after_clean_remount():
    dev = BlockDevice(num_blocks=1 << 15)
    fs = OffloadFS(dev, node="init0", shards=2)
    store = KvCacheStore(fs, chunk_blocks=2)
    cache = small_cache(1024)
    store.put([11, 12], cache)
    store.put([11, 12, 13], cache)
    fs2 = OffloadFS.mount(dev, node="init1")
    store2 = attach_store(fs2, chunk_blocks=2)
    assert {tuple(e.tokens) for e in store2.entries()} == {
        (11, 12), (11, 12, 13)}
    assert caches_equal(cache, store2.fetch([11, 12, 13]))


# ------------------------------------------------- cold-process child
def _run_serving_child(tmpdir: str) -> None:
    dev = BlockDevice(num_blocks=1 << 15)
    fs = OffloadFS(dev, node="init0", shards=2)
    store = KvCacheStore(fs, chunk_blocks=2)
    cache = {"k": jnp.arange(2048, dtype=jnp.float32)}
    good = store.put([1, 2, 3], cache)
    try:
        store.put([5, 5, 5], cache, failpoint="mid_put")
    except ServingCrash:
        pass
    orphans = sorted(ls.task_id for ls in fs._leases.values())
    dev.save(os.path.join(tmpdir, "volume.bin"))
    with open(os.path.join(tmpdir, "expect.json"), "w") as f:
        json.dump({"orphans": orphans, "good_shard": good["shard"]}, f)
    os._exit(1)  # die mid-store: no release, no cleanup, no atexit


def test_cold_process_serving_failover(tmp_path):
    """The CI ``serving-smoke`` scenario: the prefill initiator PROCESS is
    killed mid-store, a decode standby (this process) loads the volume,
    fences 100% of the orphans, and serves the surviving entry."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1, proc.stderr
    with open(tmp_path / "expect.json") as f:
        expect = json.load(f)
    assert expect["orphans"], "child must die with a lease outstanding"
    dev = BlockDevice.load(str(tmp_path / "volume.bin"))
    fs, fenced = standby_takeover(dev, node="decode0", shards=2)
    assert sorted(fenced) == expect["orphans"]  # 100% orphan fencing
    assert not fs._leases
    store = attach_store(fs, chunk_blocks=2)
    got = store.fetch([1, 2, 3])
    assert got is not None and np.array_equal(
        np.asarray(got["k"]), np.arange(2048, dtype=np.float32))
    assert not store.contains([5, 5, 5])


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--child":
        _run_serving_child(sys.argv[2])
    else:  # pragma: no cover - convenience direct run
        sys.exit(pytest.main([__file__, "-q"]))


# ---------------------------------------------------- LRU/TTL eviction
def test_lru_eviction_caps_bytes_and_recomputes_identical():
    clock = [0.0]
    dev = BlockDevice(num_blocks=1 << 15)
    fs = OffloadFS(dev, node="init0", shards=2)
    cache = small_cache()
    one = KvCacheStore(fs, root="probe", chunk_blocks=2).put(
        [0, 1], cache)["bytes"]  # blob bytes of one stored entry
    store = KvCacheStore(fs, root="kv", chunk_blocks=2,
                         capacity_bytes=int(one * 2.5),
                         clock=lambda: clock[0])
    for i in range(4):
        clock[0] = float(i)
        store.put([i, i + 1], cache)
    # capacity held: coldest entries were deleted → freed → trimmed
    assert store.stored_bytes() <= int(one * 2.5)
    assert store.stats.evictions >= 1
    assert store.fetch([0, 1]) is None  # LRU victim misses
    got = store.fetch([3, 4])  # newest survives byte-exact
    assert caches_equal(cache, got)
    # the recompute path: re-store the victim, byte-identical again
    clock[0] = 10.0
    store.put([0, 1], cache)
    assert caches_equal(cache, store.fetch([0, 1]))
    assert not fs._leases


def test_ttl_expiry_and_fetch_refreshes_lru():
    clock = [0.0]
    dev = BlockDevice(num_blocks=1 << 15)
    fs = OffloadFS(dev, node="init0", shards=2)
    cache = small_cache()
    store = KvCacheStore(fs, chunk_blocks=2, ttl_s=5.0,
                         clock=lambda: clock[0])
    store.put([1, 1], cache)
    clock[0] = 4.0
    store.put([2, 2], cache)
    assert caches_equal(cache, store.fetch([1, 1]))  # touch refreshes LRU
    clock[0] = 8.0  # [1,1] used at t=4, [2,2] at t=4: neither expired
    assert store.evict() == []
    clock[0] = 9.5  # both idle > ttl now
    victims = store.evict()
    assert len(victims) == 2 and store.stats.expirations == 2
    assert store.fetch([1, 1]) is None and store.fetch([2, 2]) is None
    assert not store.entries()
    assert not fs._leases


def test_eviction_skips_leased_entries():
    clock = [0.0]
    dev = BlockDevice(num_blocks=1 << 15)
    fs = OffloadFS(dev, node="init0", shards=2)
    cache = small_cache()
    store = KvCacheStore(fs, chunk_blocks=2, ttl_s=1.0,
                         clock=lambda: clock[0])
    store.put([5, 5], cache)
    entry = store.entries()[0]
    base = entry.replicas[min(entry.replicas)]
    clock[0] = 100.0  # way past TTL
    with fs.read_lease(f"{base}/c0"):
        assert store.evict() == []  # a decode stream still holds it
        assert store.stats.evict_skipped_leased >= 1
        assert caches_equal(cache, store.fetch([5, 5]))
    clock[0] = 200.0  # the fetch refreshed the LRU stamp: idle out again
    assert store.evict() == [entry.key]  # lease gone → eviction proceeds
    assert not fs._leases
