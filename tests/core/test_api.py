"""API-level regressions: specialization cache keys and static arguments."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api as myia


def _scale_by_first(x, ks):
    return x * ks[0]


class TestSigkeyUnhashableStatics:
    def test_sigkey_is_hashable_for_list_static(self):
        fn = myia.myia(_scale_by_first)
        key = fn._sigkey((jnp.ones(3), [2.0, 3.0]))
        hash(key)  # regression: used to raise TypeError on the list
        assert key[1][0] == "val"
        assert key[1][1] == "list"

    @pytest.mark.parametrize("backend", ["vm", "jax"])
    def test_call_with_list_static(self, backend):
        fn = myia.myia(_scale_by_first, options=myia.CompileOptions(backend=backend))
        out = fn(jnp.ones(3), [2.0, 3.0])
        np.testing.assert_allclose(np.asarray(out), 2.0 * np.ones(3))
        # second call must hit the specialization cache, not crash on it
        out2 = fn(jnp.ones(3), [2.0, 3.0])
        np.testing.assert_allclose(np.asarray(out2), 2.0 * np.ones(3))
        assert len(fn._specializations) == 1

    def test_distinct_list_statics_specialize_separately(self):
        fn = myia.myia(_scale_by_first)
        np.testing.assert_allclose(
            np.asarray(fn(jnp.ones(2), [5.0])), 5.0 * np.ones(2)
        )
        np.testing.assert_allclose(
            np.asarray(fn(jnp.ones(2), [7.0])), 7.0 * np.ones(2)
        )
        assert len(fn._specializations) == 2

    def test_large_array_statics_keyed_by_content_not_repr(self):
        """repr() elides numpy arrays > 1000 elements with '…', so two
        lists differing only in the elided region must NOT collide on one
        specialization (the static contents are baked into the runner)."""
        def pick(x, ks):
            return x * ks[0][1000]

        fn = myia.myia(pick)
        b1 = np.arange(2000.0)
        b2 = b1.copy()
        b2[1000] = 999.0
        assert repr([b1]) == repr([b2])  # the trap this guards against
        x = jnp.ones(())
        assert float(fn(x, [b1])) == pytest.approx(1000.0)
        assert float(fn(x, [b2])) == pytest.approx(999.0)
        assert len(fn._specializations) == 2

    def test_hashable_statics_still_share_cache(self):
        fn = myia.myia(_scale_by_first)
        fn(jnp.ones(2), (2.0, 3.0))
        fn(jnp.ones(2), (2.0, 3.0))
        assert len(fn._specializations) == 1
