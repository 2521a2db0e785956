"""Compile the main path's kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached, so Mosaic's refusals (block shapes, VMEM use)
surface here at no chip time.  Every kernel is compiled at the FEMNIST
client model's width (P = 6,603,710) with a 16-client cohort, and must
lower to a Mosaic ``tpu_custom_call`` rather than an interpreter loop.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and pytest-xdist
workers all import this file.  Everything compiles in the test's own
process.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import APPLY_OPTS, ops

K = 16
P = 6_603_710               # femnist_cnn parameter count
TOPK = round(P * 0.01)      # compress_topk_ratio default
CHUNK = 256                 # compress_chunk default


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip — keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def test_fed_agg_compiles_for_v5e(one_chip):
    text = _compile(lambda u, c: ops.fed_agg(u, c, interpret=False),
                    _shape(one_chip, (K, P)),
                    _shape(one_chip, (K,))).as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("opt", APPLY_OPTS)
def test_fed_agg_apply_compiles_for_v5e(one_chip, opt):
    vec = _shape(one_chip, (P,))
    text = _compile(
        lambda u, c, g, m, v: ops.fed_agg_apply(
            u, c, g, m, v, 0.01, 1.0, 0.9, 0.99, 1e-3, opt=opt,
            interpret=False),
        _shape(one_chip, (K, P)), _shape(one_chip, (K,)),
        vec, vec, vec).as_text()
    assert "tpu_custom_call" in text


def test_int8_codec_compiles_for_v5e(one_chip):
    enc = _compile(lambda x: ops.int8_encode(x, chunk=CHUNK,
                                             interpret=False),
                   _shape(one_chip, (P,))).as_text()
    n_chunks = -(-P // CHUNK)
    dec = _compile(lambda q, s: ops.int8_decode(q, s, P, interpret=False),
                   _shape(one_chip, (n_chunks, CHUNK), jnp.int8),
                   _shape(one_chip, (n_chunks,))).as_text()
    assert "tpu_custom_call" in enc
    assert "tpu_custom_call" in dec


def test_topk_encode_compiles_for_v5e(one_chip):
    text = _compile(lambda x: ops.topk_encode(x, TOPK, interpret=False),
                    _shape(one_chip, (P,))).as_text()
    assert "tpu_custom_call" in text


def test_dense_adam_compiles_for_v5e(one_chip):
    """The fused Adam step of FEMNIST's fc1 weight, vmapped over the
    cohort, with its state donated as the local-step loop carries it."""
    n_in, n_out, batch = 3136, 2048, 10
    state = _shape(one_chip, (K, n_in, n_out))
    step = jax.vmap(lambda h, dy, w, m, v, bc1, bc2: ops.dense_adam(
        h, dy, w, m, v, bc1, bc2, lr=1e-3, b1=0.9, b2=0.999, eps=1e-8,
        interpret=False), in_axes=(0, 0, 0, 0, 0, None, None))
    compiled = jax.jit(step, donate_argnums=(2, 3, 4)).lower(
        _shape(one_chip, (K, batch, n_in)), _shape(one_chip, (K, batch, n_out)),
        state, state, state, _shape(one_chip, ()),
        _shape(one_chip, ())).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # W, m and v are updated in place: no copy of a (K, n_in, n_out) state
    assert f"f32[{K},{n_in},{n_out}]{{2,1,0:T(8,128)}} copy(" not in text


def test_executor_group_step_compiles_for_v5e(one_chip, monkeypatch):
    """The vectorized executor's vmap-of-scan cohort step for the FEMNIST
    CNN at K = 16 (a few local steps), as one chip would run it: its fc1
    weight takes the fused Adam kernel, lowered to Mosaic."""
    from repro.analysis import gates
    from repro.fl.executor import VectorizedExecutor
    from repro.fl.tasks import ClassificationTask, TaskConfig
    from repro.models.small import SMALL_MODELS

    task = ClassificationTask(SMALL_MODELS["femnist_cnn"](),
                              TaskConfig(epochs=5, batch_size=10))
    params = jax.tree_util.tree_map(
        lambda s: _shape(one_chip, s.shape, s.dtype),
        jax.eval_shape(lambda: task.init_params(0)))
    assert sum(l.size for l in jax.tree_util.tree_leaves(params)) == P
    steps, batch = 4, 10
    # the backend here is the CPU: lower the kernels as the chip would
    monkeypatch.setenv(gates.PALLAS_INTERPRET, "0")
    compiled = VectorizedExecutor(task)._group_fn(0.0).lower(
        params,
        _shape(one_chip, (K, steps, batch, 28, 28, 1)),
        _shape(one_chip, (K, steps, batch), jnp.int32),
        _shape(one_chip, (K, steps, batch))).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16 * 1024 ** 3          # one v5e chip's HBM
    # one kernel per unrolled local step, in the backward pass (whose
    # name stack prefixes the kernel's name), and no copy of the state
    text = compiled.as_text()
    assert len(re.findall(r"%\w*dense_adam\w*[.\d]* = .* custom-call\(",
                          text)) == steps
    assert "f32[16,3136,2048]{2,1,0:T(8,128)} copy(" not in text
