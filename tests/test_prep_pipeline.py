"""PrepPipeline (streaming prep→train ingestion): determinism across
target counts, checkpoint/resume through OffloadDB, admission-pushback
re-routing, bounded-queue backpressure, and the streaming
``submit(specs, stream=True)`` plane it rides on."""
import time

import numpy as np

from repro.core import AcceptAll, BlockDevice, OffloadFS, RpcFabric
from repro.core.admission import RejectAll
from repro.core.rpc import FaultyFabric
from repro.core.engine import OffloadEngine
from repro.core.lsm import DBConfig, OffloadDB
from repro.core.lsm import compaction as C
from repro.core.offloader import TaskOffloader, serve_engine
from repro.data.ingest import IngestState, PrepPipeline, tokens_from_batch
from repro.data.offload_prep import OffloadPrep, stub_preprocess


def build_plane(n_targets=2, policies=None, *, mount=False, dev=None,
                fabric=None):
    dev = dev or BlockDevice(num_blocks=1 << 17)
    fs = OffloadFS.mount(dev, node="init0") if mount \
        else OffloadFS(dev, node="init0")
    fabric = fabric or RpcFabric()
    engines = []
    for t in range(n_targets):
        eng = OffloadEngine(fs, node=f"storage{t}", cache_blocks=1024)
        eng.register_stub("preprocess", stub_preprocess)
        eng.register_stub("compact", C.stub_compact)
        eng.register_stub("log_recycle", C.stub_log_recycle)
        serve_engine(eng, fabric, policies[t] if policies else AcceptAll())
        engines.append(eng)
    off = TaskOffloader(fs, fabric, node="init0",
                        targets=[e.node for e in engines])
    return dev, fs, fabric, engines, off


def make_prep(fs, off, ratio=0.25):
    return OffloadPrep(fs, off, out_size=16, offload_ratio=ratio)


# ------------------------------------------------------------ streaming
def test_submit_many_stream_resolves_per_share():
    dev, fs, fabric, engines, off = build_plane(2)
    prep = make_prep(fs, off)
    paths = prep.materialize_corpus(8, max_side=64)
    remote, local_ids = prep.plan_shares(len(paths))
    # ratio 0.25 × 8 images → 2 per target × 2 targets, 4 stay local
    assert [(t, len(ids)) for t, ids in remote] == \
        [("storage0", 2), ("storage1", 2)]
    assert local_ids == [4, 5, 6, 7]
    specs = [prep.share_spec(t, ids, paths, epoch_seed=1)
             for t, ids in remote]
    futs = off.submit(specs, stream=True)
    assert len(futs) == len(specs)
    for (target, ids), fut in zip(remote, futs):
        tensors, where = fut.result(timeout=30)
        assert where == target
        assert len(tensors) == len(ids)
    assert not fs._leases  # all released at resolution


def test_submit_stream_empty_and_legacy_plane():
    dev, fs, fabric, engines, off = build_plane(1)
    assert off.submit([], stream=True) == []
    # legacy (coalesce=False) plane still resolves futures
    off2 = TaskOffloader(fs, fabric, node="init0", coalesce=False,
                         targets=[engines[0].node])
    prep = OffloadPrep(fs, off2, out_size=16, offload_ratio=0.5)
    paths = prep.materialize_corpus(4, max_side=64)
    remote, _ = prep.plan_shares(len(paths))
    futs = off2.submit(
        [prep.share_spec(t, ids, paths) for t, ids in remote], stream=True)
    for fut in futs:
        tensors, where = fut.result(timeout=30)
        assert where == engines[0].node


# ---------------------------------------------------------- determinism
def _collect(pipe):
    return [b.copy() for b in pipe]


def test_batches_identical_regardless_of_target_count():
    golden = None
    for nt in (1, 3):
        dev, fs, fabric, engines, off = build_plane(nt)
        prep = OffloadPrep(fs, off, out_size=16, offload_ratio=0.2)
        paths = prep.materialize_corpus(24, max_side=64)
        got = _collect(PrepPipeline(prep, paths, batch=8, epochs=2, seed=7,
                                    window=2, queue_depth=2))
        assert len(got) == 6  # 3 batches/epoch × 2 epochs
        if golden is None:
            golden = got
        else:
            for a, b in zip(golden, got):
                assert np.array_equal(a, b)


def test_pipeline_matches_synchronous_minibatch_content():
    """A pipeline batch equals preprocess_minibatch on the same paths and
    seed — where a share runs never changes its bytes."""
    dev, fs, fabric, engines, off = build_plane(2)
    prep = make_prep(fs, off)
    paths = prep.materialize_corpus(8, max_side=64)
    pipe = PrepPipeline(prep, paths, batch=8, epochs=1, seed=3,
                        shuffle=False)
    got = _collect(pipe)
    assert len(got) == 1
    sync = make_prep(fs, off).preprocess_minibatch(
        paths, epoch_seed=pipe._batch_seed(0, 0))
    assert np.array_equal(got[0], sync)


# ------------------------------------------------------------- resume
def test_checkpoint_resume_roundtrip_through_offloaddb():
    dev, fs, fabric, engines, off = build_plane(2)
    prep = make_prep(fs, off)
    paths = prep.materialize_corpus(40, max_side=64)
    db = OffloadDB(fs, off, DBConfig(memtable_bytes=1 << 16))
    golden = _collect(PrepPipeline(make_prep(fs, off), paths, batch=8,
                                   epochs=2, seed=11))

    pipe = PrepPipeline(prep, paths, batch=8, epochs=2, seed=11)
    got, it = [], iter(pipe)
    for _ in range(6):  # past the first epoch boundary (5 batches/epoch)
        got.append(next(it).copy())
    pipe.checkpoint(db)
    pipe.close()
    db.flush_all()
    fs.flush_metadata()
    fabric.drain()

    # crash: everything rebuilt from the device
    del pipe, prep, db, fs, off, engines, fabric
    dev, fs2, fabric2, engines2, off2 = build_plane(2, mount=True, dev=dev)
    db2 = OffloadDB.recover(fs2, off2)
    pipe2 = PrepPipeline.resume(make_prep(fs2, off2), paths, db2)
    assert pipe2.state.epoch == 1 and pipe2.state.cursor == 1
    got.extend(_collect(pipe2))
    assert len(got) == len(golden)
    for a, b in zip(got, golden):
        assert np.array_equal(a, b)


def test_resume_preserves_shuffle_identity():
    """Regression: shuffle is part of the checkpointed identity — a
    shuffle=False pipeline must not resume into a shuffled order."""
    dev, fs, fabric, engines, off = build_plane(1)
    prep = make_prep(fs, off)
    paths = prep.materialize_corpus(16, max_side=64)
    db = OffloadDB(fs, off, DBConfig(memtable_bytes=1 << 16))
    golden = _collect(PrepPipeline(make_prep(fs, off), paths, batch=4,
                                   epochs=1, seed=3, shuffle=False))
    pipe = PrepPipeline(prep, paths, batch=4, epochs=1, seed=3,
                        shuffle=False)
    got = [next(iter(pipe)).copy()]
    pipe.checkpoint(db)
    pipe.close()
    pipe2 = PrepPipeline.resume(make_prep(fs, off), paths, db)
    assert pipe2.state.shuffle is False
    got.extend(_collect(pipe2))
    assert len(got) == len(golden)
    for a, b in zip(got, golden):
        assert np.array_equal(a, b)
    # contradicting the checkpointed identity raises
    state = PrepPipeline.load_state(db)
    try:
        PrepPipeline(make_prep(fs, off), paths, shuffle=True, state=state)
        assert False, "shuffle mismatch must raise"
    except ValueError:
        pass


def test_resume_requires_checkpoint_and_matching_corpus():
    dev, fs, fabric, engines, off = build_plane(1)
    prep = make_prep(fs, off)
    paths = prep.materialize_corpus(8, max_side=64)
    db = OffloadDB(fs, off, DBConfig(memtable_bytes=1 << 16))
    try:
        PrepPipeline.resume(prep, paths, db)
        assert False, "resume without a checkpoint must raise"
    except KeyError:
        pass
    state = IngestState(seed=1, batch=4, epochs=1, n_images=999)
    try:
        PrepPipeline(prep, paths, state=state)
        assert False, "corpus size mismatch must raise"
    except ValueError:
        pass


# ------------------------------------------------------------- reroute
def test_rejected_share_reroutes_before_local_fallback():
    dev, fs, fabric, engines, off = build_plane(
        2, policies=[RejectAll(), AcceptAll()])
    prep = make_prep(fs, off)
    paths = prep.materialize_corpus(16, max_side=64)
    got = _collect(PrepPipeline(prep, paths, batch=8, epochs=1, seed=5))
    assert len(got) == 2
    assert prep.stats["rerouted"] > 0
    assert prep.stats["rejected"] == 0  # nothing fell back to the initiator
    assert engines[0].tasks_run == 0 and engines[1].tasks_run > 0
    assert sum(prep.stats.values()) == 16
    assert off.stats.rerouted > 0


def test_reroute_wire_error_falls_back_local_and_counts_ran_local():
    """Regression: a reroute retry that dies on the wire (the alt target
    crashed after its engine came up) still completes the share locally
    AND counts it in ran_local — the stats cover every completed task.
    (An engine that never came up is a different case: no endpoint → the
    target is skipped at pick time, see least_loaded_other.)"""
    dev, fs, fabric, engines, off = build_plane(
        2, policies=[RejectAll(), AcceptAll()], fabric=FaultyFabric(seed=1))
    fabric.kill("storage1")  # endpoint registered, but dead on the wire
    prep = make_prep(fs, off)
    paths = prep.materialize_corpus(8, max_side=64)
    remote, _ = prep.plan_shares(len(paths))
    specs = [prep.share_spec("storage0", ids, paths, reroute=True)
             for t, ids in remote]
    for fut in off.submit(specs, stream=True):
        tensors, where = fut.result(timeout=30)
        assert where == off.node  # completed on the initiator
    assert off.stats.rerouted == len(specs)
    assert off.stats.ran_local == len(specs)
    assert fabric.injected["dead"] == len(specs)
    assert not fs._leases


def _run_shares(off, prep, paths, *, reroute=True):
    """Submit every planned remote share (streamed) and return the tensor
    lists in spec order plus the where-ran labels."""
    remote, _ = prep.plan_shares(len(paths))
    specs = [prep.share_spec(t, ids, paths, epoch_seed=1, reroute=reroute)
             for t, ids in remote]
    tensors, wheres = [], []
    for fut in off.submit(specs, stream=True):
        t, where = fut.result(timeout=30)
        tensors.append(t)
        wheres.append(where)
    return tensors, wheres


def test_stream_target_death_after_admission_lands_byte_identical():
    """Satellite: a target that dies AFTER a streamed share was admitted
    into the plan but BEFORE completion — the per-share lease is released
    and the share lands via reroute-or-local, byte-identical to a healthy
    run. Death is injected at delivery time, so the wire batch was already
    committed to the dead target when it fails."""
    dev, fs, fabric, engines, off = build_plane(2)
    prep = make_prep(fs, off)
    paths = prep.materialize_corpus(8, max_side=64)
    golden, _ = _run_shares(off, prep, paths)

    dev2, fs2, fabric2, engines2, off2 = build_plane(
        2, fabric=FaultyFabric(seed=7))
    prep2 = make_prep(fs2, off2)
    paths2 = prep2.materialize_corpus(8, max_side=64)
    fabric2.kill("storage1")  # dies with its batch already in flight
    got, wheres = _run_shares(off2, prep2, paths2)
    assert fabric2.injected["dead"] > 0
    assert "storage1" not in wheres  # landed via reroute or local
    assert not fs2._leases  # every per-share lease released
    assert len(got) == len(golden)
    for a, b in zip(golden, got):
        for x, y in zip(a, b):
            assert np.array_equal(x, y)


def test_stream_target_death_mid_batch_at_least_once_exactly_one_landing():
    """Satellite: the target executes the FIRST sub-call of its wire batch
    then dies mid-batch. The whole batch surfaces as a wire error, so the
    already-executed share re-runs elsewhere — at-least-once execution,
    but each share lands exactly once and bytes still match the healthy
    run (idempotent re-run under the original, still-quiesced lease)."""
    def one_image_shares(off, prep, paths):
        # one spec per image so each target's wire batch carries SEVERAL
        # sub-calls — kill_after can then strike between them
        remote, _ = prep.plan_shares(len(paths))
        specs = [prep.share_spec(t, [i], paths, epoch_seed=1, reroute=True)
                 for t, ids in remote for i in ids]
        tensors, wheres = [], []
        for fut in off.submit(specs, stream=True):
            t, where = fut.result(timeout=30)
            tensors.append(t)
            wheres.append(where)
        return tensors, wheres

    dev, fs, fabric, engines, off = build_plane(2)
    prep = make_prep(fs, off)
    paths = prep.materialize_corpus(16, max_side=64)
    golden, _ = one_image_shares(off, prep, paths)

    dev2, fs2, fabric2, engines2, off2 = build_plane(
        2, fabric=FaultyFabric(seed=7))
    prep2 = make_prep(fs2, off2)
    paths2 = prep2.materialize_corpus(16, max_side=64)
    fabric2.kill_after("storage1", 1)  # one sub-call runs, then death
    got, wheres = one_image_shares(off2, prep2, paths2)
    assert fabric2.injected["dead"] > 0
    assert engines2[1].tasks_run >= 1  # it really did execute one share
    assert "storage1" not in wheres
    assert not fs2._leases
    for a, b in zip(golden, got):
        for x, y in zip(a, b):
            assert np.array_equal(x, y)


def test_stream_death_without_reroute_surfaces_error_and_releases_lease():
    """A streamed share WITHOUT reroute=True keeps strict semantics: the
    wire error surfaces on its future — but the lease is still released
    (no leak) and the volume stays usable."""
    dev, fs, fabric, engines, off = build_plane(
        2, fabric=FaultyFabric(seed=3))
    prep = make_prep(fs, off)
    paths = prep.materialize_corpus(8, max_side=64)
    fabric.kill("storage1")
    remote, _ = prep.plan_shares(len(paths))
    specs = [prep.share_spec(t, ids, paths, epoch_seed=1)
             for t, ids in remote]
    futs = off.submit(specs, stream=True)
    outcomes = {"ok": 0, "error": 0}
    for (t, _), fut in zip(remote, futs):
        try:
            fut.result(timeout=30)
            outcomes["ok"] += 1
        except Exception:
            assert t == "storage1"
            outcomes["error"] += 1
    assert outcomes["error"] >= 1 and outcomes["ok"] >= 1
    assert not fs._leases  # errored shares released their leases too


def test_all_targets_rejecting_falls_back_local():
    dev, fs, fabric, engines, off = build_plane(
        2, policies=[RejectAll(), RejectAll()])
    prep = make_prep(fs, off)
    paths = prep.materialize_corpus(8, max_side=64)
    got = _collect(PrepPipeline(prep, paths, batch=8, epochs=1, seed=5))
    assert len(got) == 1
    # 2 images/target were submitted; both targets pushed back → initiator
    assert prep.stats["rejected"] == 4 and prep.stats["local"] == 4
    assert sum(prep.stats.values()) == 8
    assert engines[0].tasks_run == engines[1].tasks_run == 0
    assert not fs._leases


def test_offload_prep_stats_are_disjoint():
    """Satellite fix: a rejected share must not double-count as local —
    the counters partition the images exactly."""
    dev, fs, fabric, engines, off = build_plane(1, policies=[RejectAll()])
    prep = OffloadPrep(fs, off, out_size=16, offload_ratio=0.5)
    paths = prep.materialize_corpus(12, max_side=64)
    prep.preprocess_minibatch(paths, epoch_seed=3)
    assert prep.stats["rejected"] == 6 and prep.stats["local"] == 6
    assert sum(prep.stats.values()) == 12


# -------------------------------------------------------- backpressure
def test_bounded_queue_backpressure_blocks_never_drops():
    dev, fs, fabric, engines, off = build_plane(2)
    prep = make_prep(fs, off)
    paths = prep.materialize_corpus(40, max_side=64)
    pipe = PrepPipeline(prep, paths, batch=4, epochs=1, seed=9,
                        window=1, queue_depth=1)
    pipe.start()
    deadline = time.time() + 30
    while len(pipe._queue) < 1 and time.time() < deadline:
        time.sleep(0.01)
    time.sleep(0.3)  # producer gets every chance to overrun the bound
    assert len(pipe._queue) == 1  # full and HOLDING (producer blocked)
    assert pipe._queue.max_seen <= 1
    # issued ≤ delivered + queue + window + the one being assembled
    assert pipe.issued <= 0 + 1 + 1 + 1
    got = _collect(pipe)  # drain: every batch arrives exactly once
    assert len(got) == 10
    assert pipe._queue.max_seen <= 1
    golden = _collect(PrepPipeline(make_prep(fs, off), paths, batch=4,
                                   epochs=1, seed=9, window=3,
                                   queue_depth=4))
    for a, b in zip(got, golden):
        assert np.array_equal(a, b)


def test_close_mid_epoch_releases_leases_and_stops_producer():
    dev, fs, fabric, engines, off = build_plane(2)
    prep = make_prep(fs, off)
    paths = prep.materialize_corpus(32, max_side=64)
    pipe = PrepPipeline(prep, paths, batch=4, epochs=4, seed=2)
    it = iter(pipe)
    next(it)
    pipe.close()
    fabric.drain()
    assert not fs._leases
    assert pipe._thread is None
    # the volume stays usable: a fresh pipeline runs to completion
    assert len(_collect(PrepPipeline(make_prep(fs, off), paths, batch=4,
                                     epochs=1, seed=2))) == 8


# ----------------------------------------------------------- tokenizer
def test_tokens_from_batch_deterministic_and_bounded():
    batch = np.random.RandomState(0).rand(4, 16, 16, 3).astype(np.float32)
    a = tokens_from_batch(batch, vocab=512, seq_len=32)
    b = tokens_from_batch(batch.copy(), vocab=512, seq_len=32)
    assert np.array_equal(a["tokens"], b["tokens"])
    assert a["tokens"].shape == (4, 32) and a["labels"].shape == (4, 32)
    assert a["tokens"].min() >= 0 and a["tokens"].max() < 512
    assert np.array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])


# ------------------------------------------------------ adaptive window
def test_adaptive_window_controller_tracks_queue_depth():
    dev, fs, fabric, engines, off = build_plane(2)
    prep = make_prep(fs, off)
    paths = prep.materialize_corpus(8, max_side=64)
    pipe = PrepPipeline(prep, paths, batch=8, seed=1, window=2,
                        adaptive_window=True, max_window=4,
                        depth_low=1.0, depth_high=4.0)

    class _Off:
        def __init__(self):
            self.depths = {}

        def queue_depth_ewma(self):
            return dict(self.depths)

    stub = _Off()
    pipe.prep.off = stub
    # shallow targets → additive increase up to max_window, never past
    stub.depths = {"storage0": 0.1, "storage1": 0.2}
    for _ in range(10):
        pipe._adapt_window()
    assert pipe.window == 4 == pipe.window_max_seen
    # deep queues → back off toward 1, never below
    stub.depths = {"storage0": 9.0, "storage1": 7.0}
    for _ in range(10):
        pipe._adapt_window()
    assert pipe.window == 1 == pipe.window_min_seen
    # inside the band → hold
    stub.depths = {"storage0": 2.0, "storage1": 2.5}
    assert pipe._adapt_window() == 1
    # static pipelines never move
    static = PrepPipeline(prep, paths, batch=8, seed=1, window=3)
    static.prep.off = stub
    assert static._adapt_window() == 3 and static.window == 3


def test_adaptive_window_delivers_identical_batches():
    """The determinism contract: the adaptive window changes only how far
    ahead the producer runs, never batch content or order."""
    golden = None
    for adaptive in (False, True):
        dev, fs, fabric, engines, off = build_plane(2)
        prep = OffloadPrep(fs, off, out_size=16, offload_ratio=0.2)
        paths = prep.materialize_corpus(24, max_side=64)
        pipe = PrepPipeline(prep, paths, batch=8, epochs=2, seed=7,
                            window=1, adaptive_window=adaptive,
                            max_window=6, depth_low=5.0)  # always widens
        got = _collect(pipe)
        assert len(got) == 6
        if adaptive:
            assert pipe.window_max_seen > 1  # the controller actually ran
        if golden is None:
            golden = got
        else:
            for a, b in zip(golden, got):
                assert np.array_equal(a, b)
