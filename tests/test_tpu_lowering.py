"""Compile rehearsals for the TPU, with no chip attached.

The TPU compiler is installed here and compiles for a described v5e chip,
so these tests catch what interpret mode cannot: kernels Mosaic refuses
(unaligned or dynamic slices, scatters, block shapes off the (8, 128) tile)
and a float64 main path XLA cannot build. Nothing runs, so they say nothing
about results or speed — ``chip_smoke.py`` does that on the chip.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and a worker that describes it
while collecting would leave the others with a different test list. The
persistent compilation cache is off around these compiles (an entry
compiled for a described chip cannot be read back without one).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

G, N = 10, 16384  # the D=10 factor stack at the engine's capacity tier


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


@pytest.mark.parametrize("w", [1, 2, 3])
def test_banded_matvec_lowers(one_chip, w):
    from repro.kernels.banded_matvec import banded_matvec_pallas

    _compile(lambda b, x: banded_matvec_pallas(b, x, w, w, interpret=False),
             _spec(one_chip, (G, N, 2 * w + 1)), _spec(one_chip, (G, N, 1)))


@pytest.mark.parametrize("w", [1, 2, 3])
def test_band_matmul_lowers(one_chip, w):
    from repro.kernels.band_matmul import band_matmul_pallas

    band = _spec(one_chip, (G, N, 2 * w + 1))
    _compile(lambda a, b: band_matmul_pallas(a, b, w, w, w, w,
                                             interpret=False), band, band)


def test_kp_gram_lowers(one_chip):
    from repro.kernels.kp_gram import kp_gram_pallas

    _compile(lambda x, a: kp_gram_pallas(0, 1.0, x, a, interpret=False),
             _spec(one_chip, (N,)), _spec(one_chip, (N, 3)))


@pytest.mark.parametrize("kernel", ["block_cr", "banded_lu", "rgf"])
def test_excluded_kernels_still_refused(one_chip, kernel):
    """Each kernel named in ops.TPU_UNLOWERED that has a standalone wrapper
    still fails to lower. When one starts to compile, drop it from the
    exclusion (and from ROADMAP.md) so the TPU resolvers pick it up."""
    from repro.kernels.banded_lu import banded_lu_pallas
    from repro.kernels.block_cr import block_cr_pallas
    from repro.kernels.rgf import rgf_inverse_band

    assert kernel in ops.TPU_UNLOWERED
    band = _spec(one_chip, (G, N, 3))
    rhs = _spec(one_chip, (G, N, 1))
    fn = {"block_cr": lambda b, r: block_cr_pallas(b, r, 1, interpret=False),
          "banded_lu": lambda b, r: banded_lu_pallas(b, r, 1, 1,
                                                     interpret=False),
          "rgf": lambda b, r: rgf_inverse_band(b, 1, 1, 1, interpret=False),
          }[kernel]
    with pytest.raises(NotImplementedError,
                       match="Unimplemented primitive|64-bit types"):
        _compile(fn, band, rhs)


def test_fit_f64_xla_compiles(one_chip):
    """The float64 main path resolves to XLA and compiles for the chip with
    a small temp footprint (the batched-SVD KP construction it replaced
    needed 2.7 GB of temp at n=4000)."""
    from repro.core import GPConfig
    from repro.core.additive_gp import _fit_impl, resolve_config

    n, D = 256, 3
    cfg = resolve_config(GPConfig(q=0, solver_iters=8), n, jnp.float64)
    assert cfg.backend == "jax"
    specs = (_spec(one_chip, (n, D), jnp.float64),
             _spec(one_chip, (n,), jnp.float64),
             _spec(one_chip, (D,), jnp.float64),
             _spec(one_chip, (), jnp.float64))
    compiled = _compile(lambda X, Y, om, s: _fit_impl(cfg, X, Y, om, s),
                        *specs)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 * 2**20, mem
