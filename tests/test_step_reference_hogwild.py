"""The Hogwild split step (``grad_step`` then ``apply_step``) against the
float32 step reference, for every score function and loss."""

import pytest

from _step_harness import LOSSES, run_case
from repro.core.scores import MODELS


@pytest.mark.parametrize("loss", LOSSES)
@pytest.mark.parametrize("model", MODELS)
def test_hogwild_split_step_matches_reference(model, loss):
    run_case("hogwild", model, loss)
