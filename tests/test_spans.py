"""Spans on the profiler's clock (``repro.obs.spans``).

The contract under test:

- outside a ``jax.profiler.trace`` a span records nothing and formats no
  attribute;
- inside one, a traced ``soc_tuner`` run records the layer tree the
  benchmark's readers key on (``tuner.round`` > ``engine.select`` >
  ``engine.fetch``, ...) on both engine paths;
- tracing leaves the trajectory bit-identical on both engine paths;
- the fused round program carries its stages' named scopes.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BOEngine, soc_tuner
from repro.core.engine import _round_seq
from repro.core.gp import _default_params
from repro.obs import records, span
from repro.soc import VLSIFlow

from test_server import _strip_wall

T = 2
KW = dict(T=T, n=10, b=6, gp_steps=10, frontier_subset=64,
          key=jax.random.PRNGKey(3))

#: the children each engine path's ``engine.select`` records, in order
SELECT_CHILDREN = {
    "exact": ["engine.pack", "engine.launch", "engine.fetch"],
    "incremental": ["engine.pack", "engine.launch", "engine.fetch"],
}


class _Unformattable:
    def __str__(self):
        raise AssertionError("attribute formatted outside a trace")

    __repr__ = __format__ = __str__


@pytest.fixture(scope="module")
def runs(space, tmp_path_factory):
    """Per path: the run without a trace, the run inside one, and the
    records the traced run added."""
    pool = np.asarray(space.sample(jax.random.PRNGKey(42), 96))
    out = {}
    for path in ("exact", "incremental"):
        flow = VLSIFlow(space, "resnet50")
        kw = dict(KW, incremental=path == "incremental")
        n0 = len(records())
        plain = soc_tuner(space, pool, flow, **kw)
        untraced_recs = records()[n0:]
        with jax.profiler.trace(str(tmp_path_factory.mktemp(path))):
            n1 = len(records())
            traced = soc_tuner(space, pool, flow, **kw)
            recs = records()[n1:]
        out[path] = (plain, traced, recs, untraced_recs)
    return out


def test_no_trace_records_and_formats_nothing(runs):
    n0 = len(records())
    with span("outside", thing=_Unformattable()) as sp:
        sp.set(more=_Unformattable())
    assert len(records()) == n0
    for path in ("exact", "incremental"):
        assert runs[path][3] == []   # a whole untraced run recorded nothing


def test_span_records_parent_attrs_and_failure(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        n0 = len(records())
        with span("outer", k=1) as outer:
            with pytest.raises(KeyError):
                with span("inner"):
                    raise KeyError("cut")
            outer.set(late=True)
        with span("after"):
            pass
        recs = records()[n0:]
    outer_r, inner_r, after_r = recs
    assert [r.name for r in recs] == ["outer", "inner", "after"]
    assert inner_r.parent == outer_r.id and after_r.parent is None
    assert inner_r.failed and not outer_r.failed and not after_r.failed
    assert outer_r.attrs == {"k": 1, "late": True}
    assert outer_r.t0 <= inner_r.t0 <= inner_r.t1 <= outer_r.t1 <= after_r.t0
    from jax.profiler import ProfileData

    (xplane,) = tmp_path.glob("**/*.xplane.pb")
    names = {e.name for p in ProfileData.from_file(str(xplane)).planes
             if p.name.startswith("/host:") for line in p.lines
             for e in line.events}
    assert {"outer", "inner", "after"} <= names


def _children(recs, rec):
    return [r for r in recs if r.parent == rec.id]


@pytest.mark.parametrize("path", ["exact", "incremental"])
def test_traced_round_records_the_layer_tree(runs, path):
    recs = runs[path][2]
    assert all(r.t1 is not None and not r.failed for r in recs)
    roots = [r for r in recs if r.parent is None]
    assert [r.name for r in roots] == (
        ["tuner.prologue", "tuner.record", "engine.observe"]
        + ["tuner.round"] * T)
    assert [c.name for c in _children(recs, roots[0])] == ["tuner.flow"] * 2
    for i, rnd in enumerate(roots[3:]):
        assert rnd.attrs == {"round": i + 1}
        kids = _children(recs, rnd)
        assert [c.name for c in kids] == ["tuner.keys", "engine.select",
                                          "tuner.flow", "engine.observe",
                                          "tuner.record"]
        sel = kids[1]
        assert sel.attrs["path"] == path and sel.attrs["P"] % 8 == 0
        assert ("refactor" in sel.attrs) == (path == "incremental")
        assert [c.name for c in _children(recs, sel)] == \
            SELECT_CHILDREN[path]
        assert sum(c.seconds for c in kids) <= rnd.seconds


@pytest.mark.parametrize("path", ["exact", "incremental"])
def test_trajectory_identical_under_a_trace(runs, path):
    plain, traced, _, _ = runs[path]
    np.testing.assert_array_equal(plain.evaluated_rows, traced.evaluated_rows)
    np.testing.assert_array_equal(plain.y, traced.y)
    assert _strip_wall(plain.history) == _strip_wall(traced.history)


def test_round_program_carries_stage_scopes():
    rng = np.random.default_rng(0)
    pool = rng.normal(size=(40, 5)).astype(np.float32)
    eng = BOEngine(pool, incremental=True)
    eng.observe(list(range(6)), rng.normal(size=(6, 3)))
    rows_pad, y_pad, mask = eng._padded_batch(eng._rows, eng._y, 16)
    state = eng._alloc_state(_default_params(3, 5), 16, True)
    for s0 in (0, 8):
        text = _round_seq.lower(
            state, rows_pad, y_pad, mask, eng._pool_c, eng._evalm_chunks(),
            eng._base, jnp.arange(40, dtype=jnp.int32), jax.random.PRNGKey(0),
            True, 1.0, jnp.ones(3), steps=2, s=4, s0=s0,
        ).as_text(debug_info=True)
        for stage in ("fit", "factor", "v_update", "frontier", "score"):
            assert re.search(r'"jit\(_round_seq\)/%s/' % stage, text), stage
