"""Compile the main-path Pallas kernels and the whole ``pallas`` Engine
forward for one chip of a described TPU v5e, at the real KWT shapes.

Nothing runs: the TPU compiler, which ships with JAX, compiles for a
chip that is described, not attached.  It refuses what interpret mode
accepts — gathers it cannot lower, unaligned blocks, kernels over the
VMEM budget — so these tests guard the chip path at no chip time.  The
topology is described inside a fixture, never at import: only one
process may hold the TPU library, and the fixture keeps every pytest
worker collecting the same tests.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import runtime
from repro.configs import registry
from repro.kernels import ops
from repro.models import kwt

BATCH = 64
CONFIGS = ("kwt-tiny", "kwt-1")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """Entries written for a described chip cannot be read back without
    one; keep these compiles out of any persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, *avals) -> str:
    hlo = jax.jit(fn).lower(*avals).compile().as_text()
    assert "tpu_custom_call" in hlo, "no Mosaic kernel in the compiled HLO"
    return hlo


def _dims(name):
    cfg = registry.get(name).config
    return cfg, kwt.seqlen(cfg)


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("fixed", [True, False])
def test_lut_softmax_compiles(one_chip, name, fixed):
    cfg, t = _dims(name)
    scores = jax.ShapeDtypeStruct((BATCH, cfg.n_kv_heads, 1, t, t),
                                  jnp.float32, sharding=one_chip)
    _compile(lambda s: ops.lut_softmax(s, fixed=fixed, interpret=False),
             scores)


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("interp", [False, True])
def test_lut_gelu_compiles(one_chip, name, interp):
    cfg, t = _dims(name)
    h = jax.ShapeDtypeStruct((BATCH, t, cfg.d_ff), jnp.float32,
                             sharding=one_chip)
    _compile(lambda x: ops.lut_gelu(x, interp=interp, interpret=False), h)


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("batch", [BATCH, 200])
def test_int8_matmul_compiles(one_chip, name, batch):
    """The d_model x d_ff projection: K and N pad up to 128-lane MXU
    tiles, rows to a block that is a multiple of the int8 (32, 128) vreg
    packing (200 windows of 27 rows divide by no such block)."""
    cfg, t = _dims(name)
    x = jax.ShapeDtypeStruct((batch * t, cfg.d_model), jnp.int8,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((cfg.d_model, cfg.d_ff), jnp.int8,
                             sharding=one_chip)
    _compile(lambda a, b: ops.int8_matmul(a, b, x_exp=5, w_exp=6,
                                          residual_bits=16, interpret=False),
             x, w)


@pytest.mark.parametrize("name", CONFIGS)
def test_lut_attention_compiles(one_chip, name):
    cfg, t = _dims(name)
    dh = cfg.resolved_head_dim
    q = jax.ShapeDtypeStruct((BATCH, cfg.n_heads, t, dh), jnp.float32,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((BATCH, cfg.n_kv_heads, t, dh), jnp.float32,
                              sharding=one_chip)
    _compile(lambda a, b, c: ops.lut_attention(a, b, c, causal=False,
                                               interpret=False), q, kv, kv)


@pytest.mark.parametrize("name", CONFIGS)
def test_pallas_engine_forward_compiles(one_chip, name):
    """The whole compiled-Mosaic ``pallas`` plan: int8 matmul, LUT
    softmax and LUT GELU kernels inside one forward program, with the
    plan's own packed parameter shapes."""
    cfg = registry.get(name).config
    params = kwt.init_params(cfg, jax.random.PRNGKey(0))
    eng = runtime.compile_model(cfg, params, backend="pallas",
                                interpret=False)
    assert eng.interpret is False and eng.int_exec
    p_sds = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), eng.params)
    x = jax.ShapeDtypeStruct((BATCH, *cfg.input_dim), jnp.float32,
                             sharding=one_chip)
    hlo = _compile(lambda p, m: kwt.forward(p, m, eng.exec_cfg), p_sds, x)
    # one Mosaic call per int8 linear, softmax and GELU of every block
    assert hlo.count("tpu_custom_call") >= 3 * cfg.n_layers
