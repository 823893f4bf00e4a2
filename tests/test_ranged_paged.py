"""Ranged prefill, model tier, paged cache with a static table.
The tests are written once in ranged_model_tier.py; this file names their
cell."""

import pytest

from ranged_helpers import model, prompt
from ranged_model_tier import (
    CELLS,
    placed,
    references,
    test_ranged_composition_matches_prefill,
    test_ranged_matches_decode_chain,
)


@pytest.fixture(scope="module")
def cell():
    return CELLS["paged/static"]
