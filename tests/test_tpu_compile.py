"""Compile rehearsals for a described TPU v5e chip (nothing runs).

The TPU compiler is installed with JAX, and compiles for a chip that is
described and not attached: it refuses what the chip would refuse (tiling,
unsupported primitives in Pallas kernels, programs that do not fit HBM).
The topology is described only inside the fixture below, so importing this
file loads no TPU library.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.configs.base import ParallelConfig
from repro.kernels.cases import CASES
from repro.models import layers as L
from repro.models.model import build_model

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    case = CASES[name]
    args = _on(one_chip, jax.eval_shape(case.inputs, jax.random.key(0)))
    compiled = jax.jit(lambda *a: case.run(*a, interpret=False)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def minitron(topo):
    from jax.sharding import AxisType, Mesh
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    return build_model(get_config("minitron-4b"),
                       ParallelConfig(param_dtype="bfloat16",
                                      compute_dtype="bfloat16"), mesh)


def test_minitron_decode_step_fits_v5e(minitron, one_chip):
    """The full-width bf16 decode step the serving path runs (B=4, S=256)
    fits one chip's HBM: params, cache and temporaries."""
    api, cfg = minitron, minitron.cfg
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        api.param_shapes(), api.param_shardings())
    B, S = 4, 256
    kv = jax.ShapeDtypeStruct(
        (cfg.n_layers, B, S, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
    cache, tokens, pos = _on(one_chip, (
        {"k": kv, "v": kv}, jax.ShapeDtypeStruct((B, 1), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32)))
    mem = jax.jit(api.decode_fn, donate_argnums=(1,)).lower(
        params, cache, tokens, pos).compile().memory_analysis()
    assert mem.argument_size_in_bytes > 2 * api.n_params()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < \
        V5E_HBM_BYTES


def test_minitron_init_draws_leaves_without_f32_copies(minitron, one_chip):
    """Each bf16 leaf is drawn in one fused program: no float32 temporary
    of the leaf's size, so init peaks at the placed params."""
    api = minitron
    key = _on(one_chip, jax.eval_shape(lambda: jax.random.key(0)))
    leaves, treedef = jax.tree.flatten(api.defs, is_leaf=L.is_def)
    biggest = max(zip(leaves, treedef.flatten_up_to(api.param_shardings())),
                  key=lambda ds: np.prod(ds[0].shape))
    mem = L.init_leaf.lower(key, biggest[0], jnp.bfloat16,
                            biggest[1]).compile().memory_analysis()
    assert mem.output_size_in_bytes == 2 * np.prod(biggest[0].shape)
    assert mem.temp_size_in_bytes < 0.01 * mem.output_size_in_bytes
