"""Program spans (``repro.obs``): free when tracing is off, and, on one
trace recorded on the CPU, the spans each hot path emits, the op ids that
tie a worker thread's spans to the request that sent them, and the time
counters that accrue only while tracing is on."""
import glob
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from pushdown_util import build_plane as build_scan_plane
from repro import obs
from repro.core import pushdown as P
from repro.core.admission import AcceptAll
from repro.core.blockdev import BlockDevice
from repro.core.engine import OffloadEngine
from repro.core.fs import OffloadFS
from repro.core.offloader import TaskOffloader, serve_engine
from repro.core.rpc import RpcFabric
from repro.serve.kvstore import KvCacheStore, register_kv_stubs


def kv_plane(n_targets=2):
    fs = OffloadFS(BlockDevice(num_blocks=1 << 15), node="init0",
                   shards=n_targets)
    fabric = RpcFabric()
    for t in range(n_targets):
        eng = OffloadEngine(fs, node=f"storage{t}", enable_cache=False)
        register_kv_stubs(eng)
        serve_engine(eng, fabric, AcceptAll())
    off = TaskOffloader(fs, fabric, node="init0",
                        targets=[f"storage{t}" for t in range(n_targets)],
                        lb_policy="least_outstanding")
    return fs, fabric, KvCacheStore(fs, off=off, chunk_blocks=1)


def small_cache(n=4096):
    return {"k": jnp.arange(n, dtype=jnp.float32),
            "v": jnp.arange(n, dtype=jnp.float32) * 0.5}


def tiny_model():
    from repro.models.config import get_config
    from repro.models.model import build_model

    cfg = get_config("qwen3-1.7b:smoke").with_(
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
        d_ff=128, vocab_size=256, head_dim=16)
    model = build_model(cfg)
    return model, model.init(jax.random.key(0))


def test_span_off_is_the_shared_noop(monkeypatch):
    made = []
    real = jax.profiler.TraceAnnotation

    def counting(name, **kw):
        made.append(name)
        return real(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    assert not obs.ON
    a, b = obs.span("kvstore.put"), obs.span("lsm.scan")
    assert a is b and obs.op() is a
    with a:
        pass
    fn = lambda: 1  # noqa: E731
    assert obs.carry(fn) is fn
    assert made == []
    obs.enable(True)
    try:
        with obs.span("kvstore.put"):
            pass
    finally:
        obs.enable(False)
    assert made == ["kvstore.put"]


def test_time_counters_accrue_only_while_on():
    fs, fabric, store = kv_plane()
    store.put([1, 2, 3], small_cache())
    records = fs.lease_records
    assert records > 0  # always counted
    assert fabric.pickle_s == 0.0 and fs.lease_s == 0.0
    obs.enable(True)
    try:
        store.fetch([1, 2, 3])
    finally:
        obs.enable(False)
    assert fabric.pickle_s > 0.0 and fs.lease_s > 0.0
    on = (fabric.pickle_s, fs.lease_s)
    store.fetch([1, 2, 3])
    assert (fabric.pickle_s, fs.lease_s) == on
    # a fetch's read leases are not journaled; the put's write leases were
    assert fs.lease_records == records


def test_time_counters_lose_no_update_across_threads(monkeypatch):
    """With a clock that reads one second later at each read on its own
    thread, every timed pickle adds 1 s and every lease grant or release
    1 s: a lost update between threads would show as a shortfall."""
    local = threading.local()

    def clock():
        local.t = getattr(local, "t", 0.0) + 1.0
        return local.t

    fs, fabric, _ = kv_plane()
    threads, rounds = 16, 200

    def work():
        for _ in range(rounds):
            fabric._loads(fabric._dumps(b"x"))
            with fs.lease_scope((), ()):
                pass

    workers = [threading.Thread(target=work) for _ in range(threads)]
    interval = sys.getswitchinterval()
    monkeypatch.setattr(time, "perf_counter", clock)
    obs.enable(True)
    sys.setswitchinterval(1e-6)
    try:
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
        obs.enable(False)
        monkeypatch.undo()
    assert not any(w.is_alive() for w in workers)
    assert fabric.pickle_s == 2 * threads * rounds
    assert fs.lease_s == 2 * threads * rounds


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Events of every program span in one CPU trace: two served requests
    (a miss, then a hit), a put and a fetch on a 2-target plane, and a
    pushdown scan over 2 targets. Each event is (line, name, start, end,
    op), ``line`` the index of its thread's line on the host plane."""
    from repro.serve import generate

    model, params = tiny_model()
    prompt = jax.random.randint(jax.random.key(1), (1, 10), 0, 256,
                                dtype=jnp.int32)
    _, _, store = kv_plane()
    fs, _, _, db = build_scan_plane()
    for i in range(400):
        db.put(f"k{i:04d}".encode(), bytes(40))
    db.flush_all()
    # compile outside the trace
    generate(model, params, prompt, steps=3, max_len=16)
    d = str(tmp_path_factory.mktemp("trace"))
    obs.enable(True)
    jax.profiler.start_trace(d)
    try:
        generate(model, params, prompt, steps=3, max_len=16, kv_store=store)
        generate(model, params, prompt, steps=3, max_len=16, kv_store=store)
        store.put([5, 6, 7], small_cache())
        got = store.fetch([5, 6, 7])
        rows = db.scan(program=P.build_scan(b"k0100", b"k0300"),
                       pushdown=True)
    finally:
        jax.profiler.stop_trace()
        obs.enable(False)
    assert np.array_equal(np.asarray(got["v"]),
                          np.asarray(small_cache()["v"]))
    assert len(rows) == 200
    (path,) = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name in obs.SPANS or "." in e.name and e.name.split(
                        ".")[0] in ("serve", "kvstore", "lsm", "fabric",
                                    "pushdown"):
                    events.append((i, e.name, e.start_ns,
                                   e.start_ns + e.duration_ns,
                                   dict(e.stats).get("op")))
    return events


def _named(events, name):
    return [e for e in events if e[1] == name]


def test_every_span_declared_and_emitted(recorded):
    assert {e[1] for e in recorded} == set(obs.SPANS)
    assert len(set(obs.SPANS)) == len(obs.SPANS)


def test_kvstore_spans_once_per_put_and_fetch(recorded):
    # two requests (a miss: put + fetch; a hit: fetch), then a put and a
    # fetch of their own
    for name in obs.SPANS:
        if name.startswith("kvstore.put"):
            assert len(_named(recorded, name)) == 2, name
        elif name.startswith("kvstore.fetch"):
            assert len(_named(recorded, name)) == 3, name
    assert len(_named(recorded, "serve.attach")) == 2
    assert len(_named(recorded, "serve.decode")) == 2


def test_phases_nest_on_the_callers_thread(recorded):
    for parent in ("kvstore.put", "kvstore.fetch", "lsm.scan"):
        for line, _, s, e, op in _named(recorded, parent):
            kids = [k for k in recorded if k[1].startswith(parent + ".")
                    and s <= k[2] and k[3] <= e]
            assert kids and all(k[0] == line and k[4] == op for k in kids)


def test_a_request_is_one_op(recorded):
    attach, decode = (_named(recorded, n) for n in ("serve.attach",
                                                    "serve.decode"))
    for a, d in zip(attach, decode):
        assert a[4] == d[4] and a[4] is not None
    assert attach[0][4] != attach[1][4]
    # the miss's put and fetch ran inside its attach, under its op
    put = _named(recorded, "kvstore.put")[0]
    assert put[4] == attach[0][4] and attach[0][2] <= put[2]


def test_fabric_batches_carry_the_op_that_sent_them(recorded):
    for name in ("kvstore.fetch", "kvstore.put", "lsm.scan"):
        for line, _, s, e, op in _named(recorded, name):
            batches = [b for b in _named(recorded, "fabric.batch")
                       if s <= b[2] <= e]
            # one wire batch per target, each on a fabric worker's line
            assert len(batches) == 2
            assert all(b[0] != line and b[4] == op for b in batches)


def test_pushdown_spans_run_inside_the_scans_batches(recorded):
    (scan,) = _named(recorded, "lsm.scan")
    for name in ("pushdown.read", "pushdown.eval"):
        evs = _named(recorded, name)
        assert len(evs) == 2 and all(e[4] == scan[4] for e in evs)
        assert all(e[0] != scan[0] for e in evs)
