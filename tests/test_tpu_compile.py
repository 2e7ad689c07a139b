"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Each test lowers one kernel with ``interpret=False`` at the shapes the
exploration loop runs on the chip and compiles it for a described (not
attached) ``v5e:2x2`` topology, so the TPU compiler's refusals — tiling
rules, unsupported ops, VMEM overflow — show up here on a CPU host. A
compile is not a run: results and timings need the chip (``chip_smoke.py``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and pytest-xdist workers
all import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.backend import auto_chunk
from repro.soc import get_workload

#: the paper's pool (2 500 designs) padded to the 128-row tile
N_POOL = 2560
#: the large-pool cell and the engine's auto chunking of it
N_LARGE = 100_000


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip cannot be read back here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("workload", ["resnet50", "mobilenet", "transformer"])
def test_systolic_eval_compiles_for_v5e(one_chip, workload):
    from repro.kernels.systolic_eval.kernel import soc_metrics

    n_layers = get_workload(workload).shape[0]
    _compile(soc_metrics, one_chip, ((N_POOL, 26), jnp.float32),
             ((n_layers, 5), jnp.float32))


def test_pareto_count_compiles_for_v5e(one_chip):
    from repro.kernels.pareto_count.kernel import dominance_counts

    _compile(dominance_counts, one_chip, ((N_POOL, 3), jnp.float32))


@pytest.mark.parametrize("P", [128, 512])
def test_pairdist_compiles_for_v5e(one_chip, P):
    from repro.kernels.pairdist.kernel import LANE, pairdist

    _compile(pairdist, one_chip, ((N_POOL, LANE), jnp.float32),
             ((P, LANE), jnp.float32))


@pytest.mark.parametrize("P", [128, 512])
@pytest.mark.parametrize("pool", ["paper", "large"])
@pytest.mark.parametrize("reuse", ["refactor", "block", "score_only"])
def test_round_fused_compiles_for_v5e(one_chip, P, pool, reuse):
    """One chunk of the paper's pool, and the large pool under the engine's
    ``pool_chunk="auto"`` (several chunks, the chunk width off the tile)."""
    from repro.kernels.round_fused.ops import round_select

    if pool == "paper":
        nc, C = 1, N_POOL
    else:
        C = auto_chunk(N_LARGE)
        nc = -(-N_LARGE // C)
        assert nc > 1
    m, d, S = 3, 26, 10
    s0 = {"refactor": 0, "block": P // 2, "score_only": P}[reuse]

    def fn(ls, var, L, V, x, beta, ystar, pool_c, evalm_c, y_mean, y_std,
           weights):
        return round_select(ls, var, L, V, x, beta, ystar, pool_c, evalm_c,
                            y_mean, y_std, weights, s0=s0, interpret=False)

    f32 = jnp.float32
    _compile(fn, one_chip, ((m, d), f32), ((m,), f32), ((m, P, P), f32),
             ((nc, m, P, C), f32), ((P, d), f32), ((m, P), f32),
             ((S, m), f32), ((nc, C, d), f32), ((nc, C), jnp.bool_),
             ((m,), f32), ((m,), f32), ((m,), f32))
