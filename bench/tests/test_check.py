"""The check's own arithmetic: the gradient's sketch, the gaps read from it,
and the Eq. (3.1) store."""
import jax.numpy as jnp
import numpy as np
import pytest

from lib import check, model


def _sketch(v) -> dict:
    return model.flat_paths(model.sketch({"w": jnp.asarray(v)}))


def test_sketch_is_linear():
    x = jnp.arange(1000, dtype=jnp.float32).reshape(10, 100) / 1000
    y = jnp.cos(x)
    np.testing.assert_allclose(_sketch(x + y)["w"],
                               np.add(_sketch(x)["w"], _sketch(y)["w"]),
                               rtol=1e-5)


def test_directions_are_signs_and_balanced():
    one_hot = jnp.zeros(1000).at[123].set(1.0)
    assert sorted(set(np.abs(_sketch(one_hot)["w"]))) == [1.0]
    # each direction's +-1 over 65536 elements sum to about 0 (sd 256)
    ones = _sketch(jnp.ones(1 << 16))["w"]
    assert len(ones) == model.SKETCH
    assert max(abs(v) for v in ones) < 2048
    # and the directions differ from each other
    e = jnp.zeros(1 << 10).at[:8].set(1.0)
    assert len(set(_sketch(e)["w"])) > 1


def test_sketch_gap_sees_direction_where_norm_gap_does_not():
    rng = np.random.default_rng(0)
    g = rng.normal(size=4096).astype(np.float32)
    h = rng.normal(size=4096).astype(np.float32)
    h *= np.linalg.norm(g) / np.linalg.norm(h)     # same norm, new direction
    assert check._leaf_gap({"w": float(np.linalg.norm(h))},
                           {"w": float(np.linalg.norm(g))}) == pytest.approx(
                               0.0, abs=1e-6)
    assert check._sketch_gaps(_sketch(g), _sketch(g)) == {"w": 0.0}
    assert check._sketch_gaps(_sketch(h), _sketch(g))["w"] > 0.5
    # the median leaf's sketch floors a leaf that is all but zero
    gaps = check._sketch_gaps({"a": [1e-6] * 8, "b": [1.0] * 8, "c": [2.0] * 8},
                              {"a": [0.0] * 8, "b": [1.0] * 8, "c": [2.0] * 8})
    assert gaps["a"] < 1e-5
    assert check._sketch_gaps({"a": [0.0] * 8}, {"b": [0.0] * 8}) == {}


def test_store_follows_eq_3_1():
    st = check.Store(n=4, beta1=0.2, beta2=0.9)
    assert st.get(7) == (0.25, 0.25, 0)
    w = st.update(7, 2.0)
    assert w == pytest.approx(0.2 * 0.25 + 0.8 * 2.0)
    assert st.get(7) == pytest.approx((0.9 * 0.25 + 0.1 * 2.0, w, 1))
    w2 = st.update(7, 1.0, write=False)          # an answer left unwritten
    assert w2 == pytest.approx(0.2 * st.get(7)[0] + 0.8 * 1.0)
    assert st.get(7)[2] == 1
