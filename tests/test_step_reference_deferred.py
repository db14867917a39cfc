"""The single-machine step with T5 deferral on (``overlap=True``) against
the float32 step reference: each step's entity update lands one step
later, and ``flush_state`` lands the last one."""

import pytest

from _step_harness import LOSSES, run_case
from repro.core.scores import MODELS


@pytest.mark.parametrize("loss", LOSSES)
@pytest.mark.parametrize("model", MODELS)
def test_deferred_step_matches_reference(model, loss):
    run_case("deferred", model, loss)
