"""Compile the main path for a described TPU v5e chip, with no chip.

The TPU compiler is installed with JAX and compiles for a topology that
is described rather than attached.  Each test lowers one program of the
main path at its real size for one chip of a described ``v5e:2x2`` and
compiles it, which raises whatever the chip's compiler would refuse:
Mosaic layouts, float64 in a kernel, programs that do not fit.  Nothing
runs, so these tests say nothing about results or times.

The topology is described inside a module fixture (never while a module
is imported): only the process that runs these tests loads the TPU
library.  The persistent compilation cache is off around the compiles;
an entry written for a described chip cannot be read back without one.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.common import family_table
from repro.core.batched import BatchedAlertEngine
from repro.core.precision import x64_scope

S_SELECT = 65_536
MEGATICK_SESSIONS, MEGATICK_LANES, MEGATICK_CHUNK = 100_000, 4096, 48


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _lane_args(sharding, s):
    """The hetero select's ``[S]`` inputs: six float64 vectors, int64
    goal codes, a bool mask."""
    f = _spec(sharding, (s,), jnp.float64)
    return [f] * 6 + [_spec(sharding, (s,), jnp.int64),
                      _spec(sharding, (s,), jnp.bool_)]


def test_xla_hetero_select_compiles(one_chip):
    """(a) The float64 XLA select at fleet size (f64 is emulated)."""
    engine = BatchedAlertEngine(family_table("image"), None)
    assert engine._c_latency.shape == (9, 8)
    fn = functools.partial(engine._select_hetero_impl, predictions=False)
    with x64_scope():
        compiled = jax.jit(fn).lower(
            *_lane_args(one_chip, S_SELECT)).compile()
    assert compiled.memory_analysis() is not None


def test_megatick_chunk_scan_compiles(one_chip):
    """(b) One super-round ``chunk_alert`` scan over 4096 lanes with the
    100,000-session state carried."""
    from repro.traffic import MegatickGateway

    gw = MegatickGateway(family_table("image"), MEGATICK_LANES,
                         chunk=MEGATICK_CHUNK)
    fn = gw._chunk_fn("alert", None)
    s, depth = MEGATICK_SESSIONS, gw.accuracy_window - 1
    vec = _spec(one_chip, (s,), jnp.float64)
    ivec = _spec(one_chip, (s,), jnp.int64)
    carry = (vec,) * 6 + (_spec(one_chip, (s, depth), jnp.float64),
                          ivec, ivec)
    grid = lambda dt: _spec(one_chip, (MEGATICK_CHUNK, MEGATICK_LANES), dt)
    xs = (grid(jnp.bool_), grid(jnp.int64), grid(jnp.int64),
          grid(jnp.float64), grid(jnp.float64), grid(jnp.float64),
          grid(jnp.float64), grid(jnp.bool_),
          _spec(one_chip, (MEGATICK_CHUNK,), jnp.float64))
    with x64_scope():
        compiled = fn.lower(carry, vec, _spec(one_chip, (), jnp.float64),
                            xs).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("level", [1, 4])
def test_full_width_model_prefill_and_decode_compile(one_chip, level):
    """(c) ``alert-anytime-120m`` at its published widths (bf16): the
    serving engine's prefill and decode programs, batch 4, max_len 64."""
    from repro import configs
    from repro.models.registry import build_model
    from repro.serving.engine import ServeEngine

    cfg = configs.get_config("alert-anytime-120m")
    assert (cfg.d_model, cfg.n_layers, cfg.vocab) == (768, 12, 32768)
    model = build_model(cfg)
    engine = ServeEngine(model, max_len=64, batch_size=4)
    put = lambda t: jax.tree.map(
        lambda x: _spec(one_chip, x.shape, x.dtype), t)
    params = put(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    caches = put(jax.eval_shape(lambda: engine.init_caches(level)))
    pre = engine._prefill[level].lower(
        params, {"tokens": _spec(one_chip, (4, 8), jnp.int32)}).compile()
    dec = engine._decode[level].lower(
        params, _spec(one_chip, (4, 1), jnp.int32),
        _spec(one_chip, (), jnp.int32), caches).compile()
    for c in (pre, dec):
        mem = c.memory_analysis()
        assert mem.argument_size_in_bytes < 16 * 2 ** 30


def test_alert_select_compiles_to_mosaic(one_chip):
    """(d) The fused decision kernel through Mosaic (float32 in-kernel):
    its program holds the kernel as a ``tpu_custom_call``."""
    from repro.kernels.alert_select import alert_select

    engine = BatchedAlertEngine(family_table("image"), None)
    fn = functools.partial(
        alert_select, latency=engine._c_latency,
        run_power=engine._c_run_power, weights=engine._c_weights,
        q_fail=engine._c_q_fail, interpret=False)
    with x64_scope():
        compiled = jax.jit(fn).lower(
            *_lane_args(one_chip, S_SELECT)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert np.isfinite(compiled.memory_analysis().temp_size_in_bytes)


@pytest.fixture(scope="module")
def jamba_cut(one_chip):
    """The ``jamba2-mini-ep2`` cell's cut of AI21-Jamba2-Mini (8 layers,
    8 of 16 experts held, 32,768 vocabulary rows, published widths) and
    its serving engine at the cell's batch and lengths."""
    import json

    from bench import hybrid_ref
    from repro.models.registry import build_model
    from repro.serving.engine import ServeEngine

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "bench", "configs",
                           "jamba2-mini-ep2.json")) as f:
        cfg = hybrid_ref.model_config(json.load(f))
    assert (cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.n_experts_here,
            cfg.vocab) == (4096, 14336, 16, 8, 32768)
    model = build_model(cfg)
    engine = ServeEngine(model, max_len=1024 + 32, batch_size=4)
    put = lambda t: jax.tree.map(
        lambda x: _spec(one_chip, x.shape, x.dtype), t)
    params = put(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    return cfg, engine, params, put


@pytest.mark.parametrize("program", ["prefill", "decode", "weights"])
def test_jamba_cut_fits_one_chip(one_chip, jamba_cut, program):
    """(e) The Jamba cut on one chip: prefill of 4 x 1,024 tokens, a
    decode step over 1,056-position buffers, and the weight maker each
    compile with their arguments and temporaries inside 16 GiB; the
    dropless expert layer's grouped products lower as ragged dots."""
    from bench import hybrid_ref

    cfg, engine, params, put = jamba_cut
    if program == "prefill":
        c = engine._prefill[None].lower(
            params, {"tokens": _spec(one_chip, (4, 1024), jnp.int32)}
        ).compile()
        assert "ragged-dot" in c.as_text()
    elif program == "decode":
        c = engine._decode[None].lower(
            params, _spec(one_chip, (4, 1), jnp.int32),
            _spec(one_chip, (), jnp.int32),
            put(engine.cache_shapes())).compile()
        assert "ragged-dot" in c.as_text()
    else:
        k = jax.eval_shape(lambda: hybrid_ref.key_for(0))
        c = hybrid_ref._param_fn(cfg).lower(
            _spec(one_chip, k.shape, k.dtype)).compile()
        # Every weight is drawn in place: no leaf-sized temporary.
        assert c.memory_analysis().temp_size_in_bytes < 2 ** 26
    mem = c.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes + \
        mem.output_size_in_bytes - mem.alias_size_in_bytes
    assert used < 16 * 2 ** 30
