"""Multi-worker async runtime: Hogwild-style multi-trainer per machine.

Paper §3.1 runs many trainer processes per machine, all updating one shared
embedding store without locks; §3.3 overlaps CPU sampling against device
compute. The JAX analogue here:

* ``WorkerPool`` (data/pipeline.py) — N sampler threads feed one bounded
  batch queue.
* ``StoreSlot`` — the shared-store cell. ``read()`` is a lock-free reference
  read (trainers may see a *stale* published store, exactly the staleness
  the paper tolerates); ``swap(fn)`` atomically replaces the published store
  with ``fn(current)``.
* ``hogwild_train_loop`` — M trainer threads, each looping:

      batch          <- pool                 (any sampler's output)
      store          <- slot.read()          (possibly stale — tolerated)
      grads, metrics <- grad_fn(store, batch)  (the expensive part; since it
                        reads a stale store it has NO data dependency on the
                        other trainers' in-flight steps, so XLA runs these
                        concurrently)
      slot.swap(cur -> apply_fn(cur, batch, grads))   (cheap sparse apply,
                        always onto the LATEST store: staleness affects what
                        gradients were computed against, never which updates
                        survive — no update is lost)

  Without a ``(grad_fn, apply_fn)`` split the loop falls back to swapping
  the whole ``step_fn`` (read-latest -> step -> publish, serialized by data
  dependencies) — still overlaps sampling and hook work across trainers, and
  is what the distributed shard_map step uses.

Consistency: stores are immutable pytrees, so ANY published store is an
internally consistent snapshot — hooks (checkpoint/eval) receive the state
just published by the stepping trainer and run serialized under one lock
(the "barrier" of the paper's checkpoint path). The final state is read
after all trainers have joined, then hooks' ``on_end`` (flush, final save,
eval) runs single-threaded.

Because jitted JAX calls release the GIL and dispatch asynchronously, Python
threads (not processes) are enough to keep an accelerator busy; on a
many-core CPU host the independent grad computations also genuinely overlap.
"""

from __future__ import annotations

import threading
from typing import Callable, Optional, Sequence, Tuple

from repro.common import telemetry
from repro.data.pipeline import WorkerPool
from repro.launch.engine import _finish

import queue as _queue


class StoreSlot:
    """Published reference to the shared store (paper §3.1's shared memory).

    ``read``   — lock-free (a single reference load under the GIL); returns
                 whatever store was last published, possibly stale.
    ``swap``   — atomically publish ``fn(current)``. The critical section
                 only *dispatches* the (async) update, so trainers serialize
                 on microseconds of dispatch, never on device compute.
    ``version``— bumps once per successful swap (diagnostics/tests).
    """

    def __init__(self, state):
        self._state = state
        self._lock = threading.Lock()
        self.version = 0

    def read(self):
        return self._state

    def swap(self, fn: Callable):
        with self._lock:
            new = fn(self._state)
            self._state = new
            self.version += 1
        return new


class _Counter:
    """Atomic claim counter for work distribution across trainer threads."""

    def __init__(self, total: int):
        self._n = 0
        self._total = total
        self._lock = threading.Lock()

    def claim(self) -> bool:
        with self._lock:
            if self._n >= self._total:
                return False
            self._n += 1
            return True

    def unclaim(self):
        with self._lock:
            self._n -= 1


def hogwild_train_loop(
    step_fn,
    state,
    make_batch,
    n_steps: int,
    *,
    start: int = 0,
    hooks: Sequence = (),
    n_trainers: int = 1,
    n_samplers: int = 1,
    sampler_factory: Optional[Callable[[int], Callable[[], object]]] = None,
    split_step: Optional[Tuple[Callable, Callable]] = None,
    depth: int = 0,
):
    """Drive ``n_trainers`` Hogwild trainers from ``start`` to ``n_steps``.

    ``make_batch() -> (batch, stats)`` as in ``engine.train_loop``; with
    ``sampler_factory`` each sampler worker gets its own callable
    (``sampler_factory(worker_id)``) — required for ``n_samplers > 1`` so
    workers do not share an RNG.

    ``split_step = (grad_fn, apply_fn)`` enables true Hogwild staleness:
    ``grad_fn(state, batch) -> (grads, metrics)`` computed against a possibly
    stale store, ``apply_fn(state, batch, grads) -> state`` applied to the
    latest. Without it, ``step_fn(state, batch) -> (state, metrics)`` is
    swapped whole (serialized by its own data dependencies).

    Hooks run serialized under one lock with a monotone 1-based step number;
    the step number counts *completed* steps, so checkpoint/log hooks see
    the same contract as the single-trainer loop.
    """
    if start >= n_steps:
        return _finish(start, state, hooks)
    if n_samplers > 1 and sampler_factory is None:
        raise ValueError("n_samplers > 1 requires sampler_factory (each "
                         "sampler worker needs its own RNG stream)")
    factory = sampler_factory or (lambda _wid: make_batch)
    pool = WorkerPool(factory, n_workers=n_samplers,
                      depth=depth or 2 * max(n_trainers, n_samplers))
    slot = StoreSlot(state)
    todo = _Counter(n_steps - start)
    done = [start]
    hook_lock = threading.Lock()
    stop = threading.Event()
    # Trainer 0 (the caller's thread) completes step 1 before the others
    # start: jit compilation happens once, on the thread that holds any
    # thread-local JAX context (e.g. the ambient mesh of the distributed
    # driver) — not in a thundering herd of background threads.
    first_done = threading.Event()
    errors: list = []
    grad_fn, apply_fn = split_step if split_step is not None else (None, None)

    def trainer(tid: int):
        # one trace track per trainer (trainer 0 runs on the caller's thread,
        # whose thread name would otherwise label the track)
        telemetry.set_track_name(f"trainer-{tid}")
        try:
            if tid != 0:
                while not first_done.wait(0.1):
                    if stop.is_set():
                        return
            while not stop.is_set() and todo.claim():
                batch_stats = _get(pool, stop)
                if batch_stats is None:  # shut down while waiting for a batch
                    todo.unclaim()
                    return
                batch, stats = batch_stats
                if grad_fn is not None:
                    # Hogwild two-phase: grads vs stale read, apply to latest.
                    # Staleness accounting: how many other trainers' swaps
                    # landed between our read and our apply (the published
                    # versions our gradients did NOT see).
                    v_read = slot.version
                    with telemetry.span("runtime/grad"):
                        grads, metrics = grad_fn(slot.read(), batch)
                    with telemetry.span("runtime/apply"):
                        new = slot.swap(lambda cur: apply_fn(cur, batch, grads))
                    stale = slot.version - v_read - 1
                    if stale > 0:
                        telemetry.inc("runtime/stale_steps")
                        telemetry.observe("runtime/staleness", stale)
                else:
                    # whole-step swap: read-latest -> step -> publish
                    box = [None]

                    def chained(cur):
                        out, m = step_fn(cur, batch)
                        box[0] = m
                        return out

                    with telemetry.span("runtime/step"):
                        new = slot.swap(chained)
                    metrics = box[0]
                telemetry.inc("runtime/steps")
                with hook_lock:
                    done[0] += 1
                    i = done[0]
                    st = dict(stats) if stats else {}
                    st.setdefault("trainer", tid)
                    st.setdefault("queue_depth", pool.q.qsize())
                    with telemetry.span("runtime/hooks"):
                        for h in hooks:
                            h.on_step(i, new, metrics, st)
                first_done.set()
        except BaseException as e:  # propagate to the caller, release peers
            errors.append(e)
            stop.set()
        finally:
            if tid == 0:
                first_done.set()  # never leave peers waiting on a dead lead

    threads = [threading.Thread(target=trainer, args=(t,), daemon=True,
                                name=f"trainer-{t}")
               for t in range(1, n_trainers)]
    try:
        for t in threads:
            t.start()
        trainer(0)  # trainer 0 runs on the caller's thread
        for t in threads:
            t.join()
    finally:
        stop.set()
        pool.close()
    if errors:
        raise errors[0]
    return _finish(done[0], slot.read(), hooks)


def _get(pool: WorkerPool, stop: threading.Event):
    """Blocking pool.get that stays responsive to the stop event."""
    while not stop.is_set():
        try:
            return pool.get(timeout=0.1)
        except _queue.Empty:
            continue
    return None
