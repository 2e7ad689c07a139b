"""Large-pool machinery: chunked-vs-monolithic engine parity (identical
selections at any ``pool_chunk``, including masked-candidate ties), the
shard_map fleet on a forced 2-device CPU host, the TED candidate cap, and
the chunked pairdist backend helpers."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BOEngine, BatchedBOEngine
from repro.core.sampling import TED_MAX_POOL, ted_select
from repro.kernels.backend import auto_chunk, pairdist_auto, pairdist_chunked


def _pool(n, d=5, seed=0):
    return np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32)


def _flow(pool, m=3):
    W = np.random.default_rng(99).normal(size=(pool.shape[1], m))

    def f(rows):
        x = pool[np.asarray(rows)]
        return (np.tanh(x @ W)
                + 0.1 * np.sin(x.sum(1))[:, None]).astype(np.float32)

    return f


def _drive(pool, pool_chunk, *, rounds, n_init=12, gp_steps=30, seed=3):
    """Run one incremental engine for ``rounds`` selects; return the picks."""
    f = _flow(pool)
    eng = BOEngine(pool, incremental=True, gp_steps=gp_steps, warm_steps=5,
                   drift_tol=5.0, pool_chunk=pool_chunk)
    init = list(range(n_init))
    eng.observe(init, f(init))
    key = jax.random.PRNGKey(seed)
    picks = []
    for _ in range(rounds):
        key, k = jax.random.split(key)
        sub = np.arange(0, pool.shape[0], 2, dtype=np.int32)
        nxt = eng.select(k, sub_rows=sub)
        picks.append(nxt)
        eng.observe([nxt], f([nxt]))
    return picks, eng.stats


def test_chunked_matches_monolithic_small():
    """Identical pick sequences for odd / pool-sized / oversize chunks over a
    run that crosses a bucket growth (so both refactor AND block-update
    rounds are pinned), against the single-chunk (monolithic) path."""
    pool = _pool(64)
    ref, stats = _drive(pool, None, rounds=9)
    assert stats.block_updates > 0 and stats.refactors >= 1  # both regimes
    for chunk in (7, 64, 100):
        got, _ = _drive(pool, chunk, rounds=9)
        assert got == ref, f"pool_chunk={chunk} diverged: {got} != {ref}"


def test_chunked_matches_monolithic_1024():
    """Same pins at a pool size with many chunks (1024 / 177 -> 6 chunks,
    ragged tail)."""
    pool = _pool(1024, seed=1)
    ref, _ = _drive(pool, None, rounds=3, gp_steps=20)
    got, _ = _drive(pool, 177, rounds=3, gp_steps=20)
    assert got == ref


def test_chunked_tie_semantics_across_chunks():
    """Duplicated pool rows score bit-identically; monolithic argmax keeps
    the FIRST of a tie, and the chunked online reduction must reproduce that
    even when the duplicates land in different chunks — then, once the winner
    is evaluated (masked), both paths must move to the later duplicate."""
    pool = _pool(48, seed=2)
    pool[37] = pool[5]   # tie pair crossing the chunk-8 boundary
    pool[41] = pool[5]   # three-way tie
    f = _flow(pool)

    def picks_for(chunk):
        eng = BOEngine(pool, incremental=True, gp_steps=25, warm_steps=5,
                       drift_tol=5.0, pool_chunk=chunk)
        eng.observe(list(range(10, 20)), f(list(range(10, 20))))
        key = jax.random.PRNGKey(0)
        out = []
        for _ in range(4):
            key, k = jax.random.split(key)
            nxt = eng.select(k, sub_rows=np.arange(48, dtype=np.int32))
            out.append(nxt)
            eng.observe([nxt], f([nxt]))
        return out

    ref = picks_for(None)
    got = picks_for(8)
    assert got == ref
    # the tie triple really ties: if any of {5, 37, 41} is ever picked, the
    # FIRST pick among them must be row 5 (first index wins)
    tied = [p for p in ref if p in (5, 37, 41)]
    if tied:
        assert tied[0] == 5
        assert tied == sorted(tied)  # masked winners yield to later dupes


def test_batched_chunked_matches_monolithic():
    pool0 = _pool(96, seed=4)
    pools = np.stack([pool0, pool0[::-1].copy()])
    flows = [_flow(pools[0]), _flow(pools[1])]

    def picks_for(chunk):
        eng = BatchedBOEngine(pools, incremental=True, gp_steps=25,
                              warm_steps=5, drift_tol=5.0, pool_chunk=chunk)
        init = list(range(10))
        eng.observe([init, init], [flows[0](init), flows[1](init)])
        key = jax.random.PRNGKey(7)
        out = []
        for _ in range(4):
            key, k0, k1 = jax.random.split(key, 3)
            sub = np.tile(np.arange(0, 96, 2, dtype=np.int32), (2, 1))
            picks = eng.select(jnp.stack([k0, k1]), sub_rows=sub)
            out.append([int(p) for p in picks])
            eng.observe([[int(picks[0])], [int(picks[1])]],
                        [flows[0]([int(picks[0])]),
                         flows[1]([int(picks[1])])])
        return out

    assert picks_for(19) == picks_for(None)


def test_pool_chunk_requires_incremental():
    with pytest.raises(ValueError, match="incremental"):
        BOEngine(_pool(16), incremental=False, pool_chunk=4)


def test_sharded_fleet_matches_unsharded_two_devices():
    """fleet_tuner(mesh=...) over 2 forced CPU host devices reproduces the
    unsharded fleet trajectory. Runs in a subprocess because XLA_FLAGS must
    be set before jax initializes (the main test process is 1-device by
    design — see conftest)."""
    script = textwrap.dedent("""
        import jax, numpy as np
        from jax.sharding import Mesh
        from repro.core import make_space, FleetScenario, fleet_tuner
        assert jax.device_count() == 2, jax.devices()
        space = make_space()
        pool = np.asarray(space.sample(jax.random.PRNGKey(0), 64))
        scen = [FleetScenario("resnet50", seed=0),
                FleetScenario("resnet50", seed=1)]
        kw = dict(T=2, n=8, b=6, gp_steps=20, incremental=True)
        plain = fleet_tuner(space, pool, scen, **kw)
        mesh = Mesh(np.asarray(jax.devices()), ("fleet",))
        sharded = fleet_tuner(space, pool, scen, mesh=mesh, pool_chunk=13,
                              **kw)
        for a, b in zip(plain.results, sharded.results):
            np.testing.assert_array_equal(a.evaluated_rows, b.evaluated_rows)
            np.testing.assert_array_equal(a.y, b.y)
        print("SHARDED_FLEET_OK")
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=560)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "SHARDED_FLEET_OK" in res.stdout



def test_sharded_fleet_mixed_drift_rounds_match_unsharded():
    """A round where only some scenarios drift past ``drift_tol`` refactors
    those alone and block-updates the rest; under a mesh the same split is
    made per scenario, so the sharded fleet still reproduces the unsharded
    trajectory through mixed rounds (2 forced CPU host devices, in a
    subprocess as above)."""
    script = textwrap.dedent("""
        import jax, numpy as np
        from jax.sharding import Mesh
        from repro.core import make_space, FleetScenario, fleet_tuner
        assert jax.device_count() == 2, jax.devices()
        space = make_space()
        pool = np.asarray(space.sample(jax.random.PRNGKey(0), 96))
        scen = [FleetScenario(w, seed=s) for w in ("resnet50", "transformer")
                for s in (0, 1)]
        kw = dict(T=4, n=10, b=6, gp_steps=20, incremental=True,
                  drift_tol=1.0)
        plain = fleet_tuner(space, pool, scen, **kw)
        mesh = Mesh(np.asarray(jax.devices()), ("fleet",))
        sharded = fleet_tuner(space, pool, scen, mesh=mesh, **kw)
        for res in (plain, sharded):
            assert res.results[0].engine_stats["mixed_rounds"] >= 1
        for a, b in zip(plain.results, sharded.results):
            np.testing.assert_array_equal(a.evaluated_rows, b.evaluated_rows)
            np.testing.assert_array_equal(a.y, b.y)
        print("MIXED_SHARDED_FLEET_OK")
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=560)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "MIXED_SHARDED_FLEET_OK" in res.stdout

def test_fleet_mesh_validation():
    pools = np.stack([_pool(16), _pool(16)])
    with pytest.raises(ValueError, match="incremental"):
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("fleet",))
        BatchedBOEngine(pools, incremental=False, mesh=mesh)


def test_ted_cap_subsamples_huge_pools():
    """Above TED_MAX_POOL the selection runs on an even-stride subsample and
    maps back to valid, unique full-pool rows; at or below the cap the path
    is the historical one (explicit max_pool=None agrees)."""
    x = jnp.asarray(_pool(600, d=4, seed=8))
    rows_cap = ted_select(x, b=6, max_pool=128)
    assert len(set(int(r) for r in rows_cap)) == 6
    assert all(0 <= int(r) < 600 for r in rows_cap)
    # subsampled selection really comes from the stride grid
    grid = set((np.arange(128, dtype=np.int64) * 600 // 128).tolist())
    assert all(int(r) in grid for r in rows_cap)
    # small pools: cap is inert
    small = jnp.asarray(_pool(64, d=4, seed=9))
    np.testing.assert_array_equal(ted_select(small, b=5),
                                  ted_select(small, b=5, max_pool=None))
    assert TED_MAX_POOL >= 2500  # paper-scale pools must keep the exact path


def test_ted_cap_warns_and_counts_dropped():
    """No-silent-caps regression: a capped ted_select warns with the exact
    drop count, bumps the host counters, and fold_ted_stats exposes them as
    registry counters; uncapped calls touch neither."""
    import warnings

    from repro.core.sampling import TED_CAP_STATS, fold_ted_stats
    from repro.obs import MetricsRegistry

    TED_CAP_STATS["capped_calls"] = 0
    TED_CAP_STATS["dropped_candidates"] = 0
    x = jnp.asarray(_pool(300, d=4, seed=12))
    with pytest.warns(UserWarning, match=r"dropping 172 candidates"):
        rows = ted_select(x, b=4, max_pool=128)
    assert all(0 <= int(r) < 300 for r in rows)
    assert TED_CAP_STATS == {"capped_calls": 1, "dropped_candidates": 172}
    with warnings.catch_warnings():  # under the cap: silent, no counting
        warnings.simplefilter("error")
        ted_select(x, b=4, max_pool=None)
    assert TED_CAP_STATS["capped_calls"] == 1
    reg = MetricsRegistry()
    fold_ted_stats(reg)
    assert reg.counter("ted_capped_calls_total").value() == 1
    assert reg.counter("ted_dropped_candidates_total").value() == 172
    TED_CAP_STATS["capped_calls"] = 0
    TED_CAP_STATS["dropped_candidates"] = 0
    reg2 = MetricsRegistry()
    fold_ted_stats(reg2)  # zero counters register nothing at all
    assert "ted_capped_calls_total" not in reg2._instruments


def test_pairdist_chunked_bitwise_matches_auto():
    rng = np.random.default_rng(11)
    a = jnp.asarray(rng.normal(size=(37, 6)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(203, 6)), jnp.float32)
    full = pairdist_auto(a, b)
    for chunk in (17, 203, 500):
        np.testing.assert_array_equal(
            np.asarray(full),
            np.asarray(pairdist_chunked(a, b, chunk=chunk)))
    # fused-RBF form too
    np.testing.assert_array_equal(
        np.asarray(pairdist_auto(a, b, bandwidth=1.3)),
        np.asarray(pairdist_chunked(a, b, chunk=31, bandwidth=1.3)))


def test_auto_chunk_bounds():
    assert auto_chunk(100) == 100                      # tiny pools: 1 chunk
    assert auto_chunk(10**6) <= 10**6
    assert auto_chunk(10**6, budget_mb=1, floor=64) == (1 << 20) // (4 * 3 * 256)
    assert auto_chunk(10**6) >= 2048
    with pytest.raises(ValueError):
        auto_chunk(0)
