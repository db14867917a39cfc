"""The single-machine step (``make_train_step``, T5 deferral off) against
the float32 step reference, for every score function and loss."""

import pytest

from _step_harness import LOSSES, run_case
from repro.core.scores import MODELS


@pytest.mark.parametrize("loss", LOSSES)
@pytest.mark.parametrize("model", MODELS)
def test_immediate_step_matches_reference(model, loss):
    run_case("immediate", model, loss)
