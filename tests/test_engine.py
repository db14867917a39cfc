"""launch/engine: the one loop every driver uses — hooks, resume, checkpoints.

Pure-host tests (no jax): step_fn is a counter, batches are tokens.
"""

import time

from repro.launch.engine import (
    CheckpointHook, EvalHook, Hook, LoggingHook, MetricsHook, ThroughputHook,
    run_loop, train_loop,
)


def _count_step(state, batch):
    return state + 1, {"loss": float(state)}


def _batches():
    return ({"x": 0}, {"dropped": 3})


class _SaveRecorder:
    def __init__(self):
        self.calls = []

    def __call__(self, ckpt_dir, step, state):
        self.calls.append((step, state))


def test_train_loop_runs_n_steps():
    state = train_loop(_count_step, 0, _batches, 5, prefetch=False)
    assert state == 5


def test_train_loop_honors_start():
    """Resume: start=3 means only steps 4..5 run."""
    state = train_loop(_count_step, 3, _batches, 5, start=3, prefetch=False)
    assert state == 5  # 3 + 2 steps
    # fully-trained resume: no steps, hooks still finalized
    mh = MetricsHook()
    state = train_loop(_count_step, 7, _batches, 5, start=7, hooks=[mh],
                       prefetch=False)
    assert state == 7 and mh.history["loss"] == []


def test_checkpoint_hook_no_duplicate_final_save(tmp_path):
    """save_every already covering the final step -> no redundant save."""
    rec = _SaveRecorder()
    hook = CheckpointHook(str(tmp_path), save_every=2, save_fn=rec)
    train_loop(_count_step, 0, _batches, 4, hooks=[hook], prefetch=False)
    assert [s for s, _ in rec.calls] == [2, 4]


def test_checkpoint_hook_final_save_when_needed(tmp_path):
    rec = _SaveRecorder()
    hook = CheckpointHook(str(tmp_path), save_every=2, save_fn=rec)
    train_loop(_count_step, 0, _batches, 5, hooks=[hook], prefetch=False)
    assert [s for s, _ in rec.calls] == [2, 4, 5]
    # and with periodic saves off, exactly one final save
    rec2 = _SaveRecorder()
    hook2 = CheckpointHook(str(tmp_path), save_every=0, save_fn=rec2)
    train_loop(_count_step, 0, _batches, 3, hooks=[hook2], prefetch=False)
    assert [s for s, _ in rec2.calls] == [3]


def test_checkpoint_hook_flush_fn_applied(tmp_path):
    """Deferred (T5) state must be flushed into every checkpoint."""
    rec = _SaveRecorder()
    hook = CheckpointHook(str(tmp_path), save_every=2, save_fn=rec,
                          flush_fn=lambda s: s + 1000)
    train_loop(_count_step, 0, _batches, 2, hooks=[hook], prefetch=False)
    assert rec.calls == [(2, 1002)]


def test_metrics_hook_records_history():
    mh = MetricsHook(["loss"])
    train_loop(_count_step, 0, _batches, 4, hooks=[mh], prefetch=False)
    assert mh.history["loss"] == [0.0, 1.0, 2.0, 3.0]


def test_logging_hook_reports_drops():
    lines = []
    lh = LoggingHook(log_every=2, batch_size=10, print_fn=lines.append)
    train_loop(_count_step, 0, _batches, 4, hooks=[lh], prefetch=False)
    assert len(lines) == 2
    assert "loss" in lines[0] and "drop" in lines[0]
    # 3 dropped per step of 10 samples = 30%
    assert "30.00%" in lines[1]


def test_on_end_can_replace_state():
    class Flusher(Hook):
        def on_end(self, i, state):
            return state * 100

    state = train_loop(_count_step, 0, _batches, 2, hooks=[Flusher()],
                       prefetch=False)
    assert state == 200


def test_run_loop_indices_and_hooks():
    seen = []

    def step(i, state):
        seen.append(i)
        return state + i, {"loss": 0.0}

    mh = MetricsHook()
    state = run_loop(step, 0, 4, hooks=[mh])
    assert seen == [0, 1, 2, 3]
    assert state == 6
    assert len(mh.history["loss"]) == 4


def test_train_loop_prefetches():
    """The default prefetching path produces identical results."""
    state = train_loop(_count_step, 0, _batches, 6)
    assert state == 6


def test_train_loop_bounds_steps_in_flight():
    """After dispatching step i the loop waits for step i - 2 to finish, so
    no more than two steps run ahead of the device, however far away the
    next hook that syncs is."""
    from repro.launch import engine

    events = []

    class Done:
        def __init__(self, i):
            self.i = i

        def block_until_ready(self):
            events.append(("wait", self.i))
            return self

    def step(state, batch):
        events.append(("step", state + 1))
        return state + 1, {"loss": Done(state + 1)}

    assert train_loop(step, 0, _batches, 6, prefetch=False) == 6
    k = engine._IN_FLIGHT
    want = []
    for i in range(1, 7):
        want.append(("step", i))
        if i > k:
            want.append(("wait", i - k))
    assert events == want


def test_eval_hook_periodic_and_final():
    evals = []
    hook = EvalHook(lambda state: evals.append(state), eval_every=2)
    train_loop(_count_step, 0, _batches, 5, hooks=[hook], prefetch=False)
    assert evals == [2, 4, 5]  # steps 2, 4 periodic + final at 5


def test_eval_hook_skips_duplicate_final_eval():
    evals = []
    hook = EvalHook(lambda state: evals.append(state), eval_every=2)
    train_loop(_count_step, 0, _batches, 4, hooks=[hook], prefetch=False)
    assert evals == [2, 4]  # periodic eval at 4 already covered the end


def test_eval_hook_default_is_final_only():
    evals = []
    hook = EvalHook(lambda state: evals.append(state))
    train_loop(_count_step, 0, _batches, 5, hooks=[hook], prefetch=False)
    assert evals == [5]


def test_throughput_hook_clock_starts_at_first_step():
    """Setup time between construction and the loop (e.g. jit compile) must
    not pollute the reported rate."""
    lines = []
    hook = ThroughputHook(items_per_step=10, label="tok", print_fn=lines.append)
    assert hook.t0 is None
    time.sleep(0.25)  # "compile time" before the first step
    run_loop(lambda i, s: (s + 1, {"loss": 0.0}), 0, 4, hooks=[hook])
    assert len(lines) == 1
    rate = float(lines[0].split("-> ")[1].split(" ")[0])
    # 4 steps of ~0s each: with a lazy t0 the rate is huge; with the old
    # construction-time t0 it would be bounded by ~4*10/0.25 = 160 tok/s
    assert rate > 1000


def test_logging_hook_reports_trainer_count():
    """Fed multi-trainer stats (as the Hogwild runtime emits them), the log
    line reports how many trainers contributed and the queue depth."""
    lines = []
    lh = LoggingHook(log_every=4, print_fn=lines.append)
    for i in range(1, 5):
        lh.on_step(i, i, {"loss": 0.0},
                   {"trainer": i % 2, "queue_depth": 3})
    assert lines and "2 trainers" in lines[0] and "q=3" in lines[0]


def test_train_loop_multi_trainer_pure_host():
    """train_loop transparently delegates to the Hogwild runtime."""
    mh = MetricsHook()
    state = train_loop(_count_step, 0, _batches, 12, hooks=[mh], n_trainers=3)
    assert state == 12
    assert len(mh.history["loss"]) == 12
