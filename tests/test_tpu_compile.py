"""Compile-only checks for a described TPU v5e: both clustering kernels at
the paper's data widths, and the engine's fit driver on both sweep paths.

Nothing here runs on a chip.  The TPU compiler is installed with jaxlib and
compiles for a topology that is described, not attached, so Mosaic's
refusals (block shapes, unsupported vector ops, VMEM over-use) surface here
instead of on the chip.  The topology is described inside a fixture: the
TPU library admits one process at a time, and only the test worker that
runs this file loads it.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.engine import EngineConfig, _fit, get_algorithm
from repro.core.em_gmm import GMMParams
from repro.kernels.gmm_estep.ops import gmm_estep
from repro.kernels.kmeans_assign.ops import kmeans_assign

# (name, N, D, K): skin and poker at their published sizes, land-use pixels
# at one 438×406 image (paper §5.4)
WIDTHS = [("skin", 245_057, 4, 2), ("poker", 1_025_010, 11, 10),
          ("landuse", 438 * 406, 3, 6)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        prev = jax.config.jax_enable_compilation_cache
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "can't"
            jax.config.update("jax_enable_compilation_cache", prev)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("restarts", [1, 4])
@pytest.mark.parametrize("name,n,d,k", WIDTHS)
def test_kmeans_assign_compiles_for_tpu(one_chip, name, n, d, k, restarts):
    del name
    c = (restarts, k, d) if restarts > 1 else (k, d)
    hlo = _compiled_text(
        lambda x, w, c: kmeans_assign(x, c, mask=w, backend="tpu"),
        _spec((n, d), one_chip), _spec((n,), one_chip), _spec(c, one_chip))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("restarts", [1, 4])
@pytest.mark.parametrize("name,n,d,k", WIDTHS)
def test_gmm_estep_compiles_for_tpu(one_chip, name, n, d, k, restarts):
    del name
    p = (restarts, k, d) if restarts > 1 else (k, d)
    hlo = _compiled_text(
        lambda x, w, m, v, lw: gmm_estep(x, m, v, lw, mask=w, backend="tpu"),
        _spec((n, d), one_chip), _spec((n,), one_chip),
        _spec(p, one_chip), _spec(p, one_chip), _spec(p[:-1], one_chip))
    assert "tpu_custom_call" in hlo


def test_restart_fleet_with_per_restart_points_compiles(one_chip):
    """Minibatch restart fleets draw different chunks per restart: points,
    weights and params all ride the kernels' restart grid axis."""
    r, n, d, k = 4, 1024 * 16, 11, 10
    fn = jax.vmap(lambda x, w, c: kmeans_assign(x, c, mask=w, backend="tpu"))
    hlo = _compiled_text(fn, _spec((r, n, d), one_chip),
                         _spec((r, n), one_chip), _spec((r, k, d), one_chip))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("algorithm", ["kmeans", "em"])
def test_engine_fit_compiles_for_tpu(one_chip, algorithm, use_kernel):
    """The engine's fit driver at skin's published size, 8 chunks: the
    kernel path carries the Mosaic call, the default path none."""
    n, d, k = 245_057, 4, 2
    kw = dict(use_kernel=True, kernel_backend="tpu") if use_kernel else {}
    cfg = EngineConfig(max_iters=50, chunks=8, **kw)
    if algorithm == "kmeans":
        params = _spec((k, d), one_chip)
    else:
        params = GMMParams(_spec((k, d), one_chip), _spec((k, d), one_chip),
                           _spec((k,), one_chip))
    hlo = _fit.lower(_spec((n, d), one_chip), params,
                     jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip),
                     get_algorithm(algorithm), cfg).compile().as_text()
    assert ("tpu_custom_call" in hlo) == use_kernel
