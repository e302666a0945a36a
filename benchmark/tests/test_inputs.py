"""Inputs and weights come from the seed: the same seed gives the same ones,
any whole number up to a little over 2**31 is a seed, and every seed gets
the same sizes."""
import jax
import numpy as np

from harness import state, traffic

MIX = {"kind": "train_closed", "nodes": 48, "batch": 2}


def test_same_seed_same_structure_and_other_seed_another():
    big = 2**31 + 12345
    a, b = (traffic.train_structure(MIX, big, 16) for _ in range(2))
    c = traffic.train_structure(MIX, big + 1, 16)
    for x, y, z in zip(a, b, c):
        np.testing.assert_array_equal(x, y)
        assert x.shape == z.shape
    assert not np.array_equal(a[0], c[0]) and not np.array_equal(a[1], c[1])
    assert a[0].shape == (2, 48, 16) and a[1].shape == (2, 48, 3)
    # the rows of a batch all differ
    assert not np.array_equal(a[1][0], a[1][1])
    np.testing.assert_allclose(a[1].mean(axis=1), 0.0, atol=1e-5)


def test_weights_follow_the_seed_and_the_leaf_rule():
    abstract = {'conv': {'w3_1_2': jax.ShapeDtypeStruct((128, 24, 8), 'f4'),
                         'b3_1_2': jax.ShapeDtypeStruct((24, 8), 'f4')},
                'to_q': {'w0': jax.ShapeDtypeStruct((64, 32), 'f4')},
                'norm': {'scale0': jax.ShapeDtypeStruct((1, 1, 64), 'f4')}}
    fill = state.make_fill(abstract)
    p = fill(state.prng_key(2**31 + 7, 0))
    q = fill(state.prng_key(2**31 + 7, 0))
    r = fill(state.prng_key(2**31 + 8, 0))
    assert all(np.array_equal(x, y) for x, y in zip(
        jax.tree_util.tree_leaves(p), jax.tree_util.tree_leaves(q)))
    assert not np.array_equal(p['to_q']['w0'], r['to_q']['w0'])
    # fan-in over both contracted axes of w3, over the input of a linear map
    assert abs(float(p['conv']['w3_1_2'].std()) * (128 * 24) ** 0.5 - 1) < .05
    assert abs(float(p['to_q']['w0'].std()) * 64 ** 0.5 - 1) < 0.1
    assert abs(float(p['norm']['scale0'].mean()) - 1) < 0.1
    assert state.param_count(abstract) == 128 * 24 * 8 + 24 * 8 + 64 * 32 + 64
