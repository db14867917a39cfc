"""The naive-negative step (``naive_train_step``, independent negatives per
triplet) against the float32 step reference, for every score function and
loss."""

import pytest

from _step_harness import LOSSES, run_case
from repro.core.scores import MODELS


@pytest.mark.parametrize("loss", LOSSES)
@pytest.mark.parametrize("model", MODELS)
def test_naive_step_matches_reference(model, loss):
    run_case("naive", model, loss)
