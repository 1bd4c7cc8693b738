"""Differential harness, property-style threshold streams (the corpus and
the stream itself: ``tests/test_query_differential_threshold.py`` and
``tests/_differential.py``)."""

from tests._differential import ROW_COUNTS, _run_threshold_differential
from tests._hypothesis_compat import given, settings, st


@settings(max_examples=4, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**16),
    n=st.sampled_from(ROW_COUNTS),
    policy=st.sampled_from(["roundrobin", "range"]),
)
def test_threshold_differential_property(seed, n, policy):
    """Property-style threshold streams: hypothesis drives seeds when
    installed; the shim skips this (the corpus file still runs)."""
    _run_threshold_differential(seed, n, policy)
