"""Smoke run of the served device path on one TPU.

    python chip_smoke.py

Drives the system's two device-heavy paths once through their normal entry
points and checks what comes out:

  1. KV serving at qwen3-1.7b's published widths (28 layers, d=2048, vocab
     151936; random weights from a seed): ``serve.generate`` over a
     ``KvCacheStore`` on a 4-target offload plane. Two prompts are each
     served cold (prefill → put → fetch → decode) and warm (attach, no
     prefill); tokens must equal ``generate`` without a store, the fetched
     cache must be byte-equal to the prefill's, the merge kernel must have
     assembled the fetches, and the second prompt must compile nothing.
  2. Pushdown merge: fig21's striped corpus on 4 targets, where a pushdown
     scan must return the rows block shipping does, and ``ops.merge_sorted``
     on padded and tiled run lengths against a numpy merge.

It needs a TPU: with no TPU it exits non-zero before any other JAX work,
and the kernels never run in interpret mode. The compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache/`` beside this file.
The last line of stdout is the JSON result; every earlier line is a log,
and the wall times there are smoke timings, not metrics.
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 0
BATCH, PROMPT_LEN, DECODE_STEPS = 4, 1024, 16
N_PROMPTS = 2
# two stored caches of ~0.48 GB each (~117 Ki blocks), each on one stripe
VOLUME_BLOCKS = 1 << 19
PUSHDOWN_KEYS = 8000


class SmokeFailure(RuntimeError):
    pass


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


class CompileLog:
    """Counts XLA compiles (persistent-cache loads included) and cache hits
    through ``jax.monitoring``."""

    def __init__(self):
        self.compiles, self.secs, self.hits, self.names = 0, 0.0, 0, []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.secs += secs
            self.names.append(str(kw.get("fun_name", "?")))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def report(self, label: str) -> None:
        print(f"compiles [{label}]: {self.compiles} in {self.secs:.3f} s, "
              f"persistent-cache hits {self.hits}", flush=True)


def device_gate():
    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found {d.platform}")
    return d, len(devs)


def _leaves_byte_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    if len(la) != len(lb):
        return False
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape or x.dtype != y.dtype or x.tobytes() != y.tobytes():
            return False
    return True


def serve_phase(log: CompileLog, cfg, *, batch: int, prompt_len: int,
                steps: int, num_blocks: int) -> None:
    from benchmarks import fig20_kv_serving as fig20
    from repro.kernels import ops
    from repro.models.model import build_model
    from repro.serve.kvstore import KvCacheStore
    from repro.serve.step import generate, jitted_steps

    print(f"serving: {cfg.name} layers={cfg.num_layers} d={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads} "
          f"head_dim={cfg.head_dim} vocab={cfg.vocab_size}; "
          f"B={batch} S={prompt_len} decode={steps}", flush=True)
    max_len = prompt_len + steps
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(model.init)(jax.random.key(SEED)))
    print(f"  params {model.n_params() / 1e9:.3f} B, built in "
          f"{time.perf_counter() - t0:.3f} s (smoke wall time)", flush=True)
    prompts = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, (N_PROMPTS, batch, prompt_len), dtype=np.int32)
    _dev, fs, _fabric, _engines, off = fig20.build_plane(num_blocks=num_blocks)
    store = KvCacheStore(fs, off=off, chunk_blocks=32, placement="prefix")
    prefill, decode = jitted_steps(cfg, max_len)

    for i, prompt in enumerate(prompts):
        before = log.compiles
        t0 = time.perf_counter()
        ref = np.asarray(generate(model, params, prompt, steps=steps,
                                  max_len=max_len))
        t1 = time.perf_counter()
        cold = np.asarray(generate(model, params, prompt, steps=steps,
                                   max_len=max_len, kv_store=store))
        t2 = time.perf_counter()
        warm = np.asarray(generate(model, params, prompt, steps=steps,
                                   max_len=max_len, kv_store=store))
        t3 = time.perf_counter()
        print(f"prompt {i}: in-memory {t1 - t0:.3f} s, cold {t2 - t1:.3f} s, "
              f"warm {t3 - t2:.3f} s (smoke wall times)", flush=True)
        require(ref.shape == (batch, steps), f"tokens shape {ref.shape}")
        require(np.array_equal(cold, ref) and np.array_equal(warm, ref),
                "cold, warm and in-memory tokens identical")
        logits, cache = prefill(params, {"tokens": prompt})
        fetched = store.fetch(prompt)
        require(_leaves_byte_equal(fetched, cache),
                "fetched cache leaves byte-equal to the prefill's")
        _, step_logits, _ = decode(params, fetched, ref[:, :1])
        require(bool(np.isfinite(np.asarray(logits, np.float32)).all()
                     and np.isfinite(np.asarray(step_logits, np.float32)).all()),
                "prefill and decode logits finite")
        del cache, fetched, logits, step_logits
        if i == 0:
            # fetch assembly merges runs whose lengths depend on arrival
            # interleaving: compile every kernel shape a fetch can reach
            nchunks = store.entries()[0].nchunks
            n = ops.MERGE_MIN_RUN
            while n // 2 < nchunks:
                ops.merge_sorted(*(np.arange(n, dtype=np.int32),) * 4)
                n *= 2
            log.report("first prompt, merge shapes warmed")
        else:
            new = log.compiles - before
            require(new == 0, f"repeat request compiled {new} programs "
                              f"{log.names[before:]}")
    st = store.stats
    print(f"  store: puts={st.puts} fetches={st.fetches} "
          f"fetch_chunks={st.fetch_chunks} merge_runs={st.merge_runs}",
          flush=True)
    require(st.merge_runs > st.fetches,
            f"merge kernel assembled fetches ({st.merge_runs} runs over "
            f"{st.fetches} fetches)")


def pushdown_phase() -> None:
    from benchmarks import fig21_pushdown as fig21
    from repro.core import pushdown as P
    from repro.kernels import ops

    t0 = time.perf_counter()
    _fs, _fabric, engines, db = fig21.build_plane(fig21.N_TARGETS)
    fig21.load_corpus(db, PUSHDOWN_KEYS)
    prog = P.build_scan(b"user", b"userz", where=fig21.tier_filter("sel10"))
    rows_local = db.scan(program=prog, pushdown=False)
    rows_push = db.scan(program=prog, pushdown=True)
    print(f"pushdown: {PUSHDOWN_KEYS} keys on {fig21.N_TARGETS} targets, "
          f"{len(rows_push)} rows at ~10% selectivity, "
          f"{time.perf_counter() - t0:.3f} s (smoke wall time)", flush=True)
    fanout = sum(1 for e in engines if e.pushdown_scans)
    require(fanout > 1, f"pushdown scan merged {fanout} target streams")
    require(len(rows_push) > 0 and rows_push == rows_local,
            "pushdown rows equal block shipping's")

    rng = np.random.default_rng(SEED)
    max_run = ops.MERGE_MAX_RUN
    for label, na, nb in (("padded", 300, 1000), ("padded", 37, 5000),
                          ("tiled", max_run + 4321, 2 * max_run + 7)):
        keys = rng.choice(1 << 30, na + nb, replace=False).astype(np.int32)
        a, b = np.sort(keys[:na]), np.sort(keys[na:])
        av = np.arange(na, dtype=np.int32)
        bv = np.arange(na, na + nb, dtype=np.int32)
        mk, mv = ops.merge_sorted(a, av, b, bv)
        ck, cv = np.concatenate([a, b]), np.concatenate([av, bv])
        order = np.argsort(ck, kind="stable")
        require(np.array_equal(mk, ck[order]) and np.array_equal(mv, cv[order]),
                f"merge_sorted {label} ({na}+{nb}) equals numpy")


def main() -> int:
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(HERE, ".jax_cache"))
    dev, count = device_gate()
    sys.path[:0] = [os.path.join(HERE, "src"), HERE]
    from repro.kernels import ops
    from repro.models.config import get_config

    if ops.interpret_mode():
        raise SystemExit("chip_smoke: kernels would run in interpret mode")
    log = CompileLog()
    t0 = time.perf_counter()
    serve_phase(log, get_config("qwen3-1.7b"), batch=BATCH,
                prompt_len=PROMPT_LEN, steps=DECODE_STEPS,
                num_blocks=VOLUME_BLOCKS)
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use after serving: "
          f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)
    pushdown_phase()
    log.report("whole run")
    print(f"total {time.perf_counter() - t0:.3f} s (smoke wall time)",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
