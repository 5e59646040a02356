"""The benchmark's corpus generator is a faithful copy of the program's."""

import numpy as np

from bench import corpus


def test_copy_reproduces_program_generator():
    from repro.data.synthetic import generate_collection_device, splade_config

    seed = 4242
    want = generate_collection_device(splade_config(n_docs=700, n_queries=9, seed=seed), "f16")
    got = corpus.generate(corpus.Profile(n_docs=700, n_queries=9, seed=seed), np.float16)
    np.testing.assert_array_equal(got.components, want.fwd.components)
    np.testing.assert_array_equal(got.values, want.fwd.values)
    np.testing.assert_array_equal(got.offsets, want.fwd.offsets)
    assert got.values.dtype == want.fwd.values.dtype == np.float16
    assert len(got.query_comps) == want.n_queries == 9
    for i in range(9):
        np.testing.assert_array_equal(got.query_comps[i], want.query_comps[i])
        np.testing.assert_array_equal(got.query_vals[i], want.query_vals[i])
        np.testing.assert_array_equal(got.queries_dense([i])[0], want.query_dense(i))
