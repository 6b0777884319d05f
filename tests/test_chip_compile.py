"""Compile rehearsal for a TPU v5e that is described, not attached.

Each test lowers a kernel of the served path, or a whole serving step of
qwen3-1.7b at its published widths, and compiles it with the chip's own
compiler. That catches what interpret mode cannot: block shapes that break
the (8, 128) tiling rule, primitives Mosaic cannot lower, kernels past the
VMEM budget, and steps that do not fit the chip's 16 GB of HBM. Nothing
runs, so nothing here says anything about results or times.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import kvmerge
from repro.kernels import preprocess as pp
from repro.kernels.ops import MERGE_MAX_RUN, MERGE_MIN_RUN
from repro.models.config import get_config
from repro.models.model import build_model
from repro.serve.step import make_decode_step, make_prefill_step

V5E_HBM_BYTES = 16e9
# the shapes chip_smoke.py serves: B prompts of S tokens, 16 decode steps
BATCH, PROMPT_LEN, MAX_LEN = 4, 1024, 1040


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n", [MERGE_MIN_RUN, MERGE_MAX_RUN])
def test_bitonic_merge_compiles(one_chip, n):
    shape = (2 * n // kvmerge.LANES, kvmerge.LANES)
    compiled = _compile(kvmerge.bitonic_merge,
                        _shape(shape, jnp.int32, one_chip),
                        _shape(shape, jnp.int32, one_chip))
    assert _has_kernel(compiled)


def test_preprocess_plane_compiles(one_chip):
    C, H, W, out = 3, 375, 500, 224
    compiled = _compile(pp.preprocess_plane,
                        _shape((C, H, W), jnp.float32, one_chip),
                        _shape((out, H), jnp.float32, one_chip),
                        _shape((W, out), jnp.float32, one_chip),
                        _shape((C, 1, 1), jnp.float32, one_chip),
                        _shape((C, 1, 1), jnp.float32, one_chip))
    assert _has_kernel(compiled)


def test_flash_attention_compiles_at_qwen3_widths(one_chip):
    cfg = get_config("qwen3-1.7b")
    B, S, D = 1, 2048, cfg.head_dim
    compiled = _compile(
        fa.flash_attention,
        _shape((B * cfg.num_heads, S, D), jnp.bfloat16, one_chip),
        _shape((B * cfg.num_kv_heads, S, D), jnp.bfloat16, one_chip),
        _shape((B * cfg.num_kv_heads, S, D), jnp.bfloat16, one_chip),
    )
    assert _has_kernel(compiled)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


def test_qwen3_serving_steps_fit_one_chip(one_chip):
    """Prefill and decode of the full-width model, depth uncut, fit 16 GB."""
    cfg = get_config("qwen3-1.7b")
    assert (cfg.num_layers, cfg.d_model, cfg.vocab_size) == (28, 2048, 151936)
    model = build_model(cfg)

    def place(tree):
        return jax.tree.map(lambda s: _shape(s.shape, s.dtype, one_chip), tree)

    params = place(jax.eval_shape(model.init, jax.random.key(0)))
    batch = {"tokens": _shape((BATCH, PROMPT_LEN), jnp.int32, one_chip)}
    prefill = make_prefill_step(model, MAX_LEN)
    _, cache = jax.eval_shape(prefill, params, batch)
    compiled = _compile(prefill, params, batch)
    assert _device_bytes(compiled) < V5E_HBM_BYTES

    tok = _shape((BATCH, 1), jnp.int32, one_chip)
    compiled = _compile(make_decode_step(model), params, place(cache), tok)
    assert _device_bytes(compiled) < V5E_HBM_BYTES
