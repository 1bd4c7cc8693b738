"""Differential harness, threshold streams: random k-of-N ``AtLeast``
predicates with appends and deletes (see
``tests/test_query_differential.py``).  The property-style streams are in
``tests/test_query_differential_threshold_property.py``: each runs for
minutes, and a file of its own lets a parallel run start it on a free
worker."""

import pytest

from tests._differential import _run_threshold_differential

THRESHOLD_CORPUS = [
    (61, 97, "roundrobin"),
    (62, 130, "range"),
    (63, 31, "roundrobin"),
]


@pytest.mark.parametrize("seed,n,policy", THRESHOLD_CORPUS)
def test_threshold_differential_corpus(seed, n, policy):
    """Deterministic k-of-N threshold stream corpus: always runs."""
    _run_threshold_differential(seed, n, policy)
