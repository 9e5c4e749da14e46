"""The process's main thread, lent to whoever has work that belongs there.

A worker's main thread only waits for its raylet's link to drop: tasks run on
executor threads and an actor's constructor on a thread of its own
(`core/worker.py:_init_actor`). One kind of work is much faster on the main
thread than anywhere else: on a TPU v5e the client's `deserialize_executable`,
the read of a compiled program from jax's persistent cache, took 0.46-0.59 s
there and 2.1-7.5 s for the same 43-51 MiB program on any other thread, the
one that opened the chip included (PR 64, `ci/chip_calls/pr64/` calls 1-2;
PERF.md section 6). So the worker's main thread serves a queue while it
waits (`serve`), and `models/programs.py` hands its cache reads to it
(`submit`). A process whose main thread does not serve (a script, a test)
lends nothing: `submit` returns None and the caller keeps its work.
"""

from __future__ import annotations

import concurrent.futures
import queue
import threading
from typing import Callable, Optional

_jobs: Optional["queue.SimpleQueue"] = None


def serve(until: Callable[[], bool], poll_s: float = 0.5) -> None:
    """Runs submitted jobs on the calling thread, which must be the main one,
    until `until()` holds (looked at between jobs and every `poll_s`)."""
    global _jobs
    if threading.current_thread() is not threading.main_thread():
        raise RuntimeError("only the main thread can be lent")
    _jobs = jobs = queue.SimpleQueue()
    try:
        while not until():
            try:
                future, fn, args = jobs.get(timeout=poll_s)
            except queue.Empty:
                continue
            if future.set_running_or_notify_cancel():
                try:
                    future.set_result(fn(*args))
                except BaseException as e:  # noqa: BLE001 - the submitter's to handle
                    future.set_exception(e)
                    if not isinstance(e, Exception):
                        raise   # an interrupt is the main thread's own too
    finally:
        _jobs = None
        while not jobs.empty():   # nobody waits for ever on a thread that left
            jobs.get_nowait()[0].cancel()


def submit(fn: Callable, *args) -> Optional[concurrent.futures.Future]:
    """`fn(*args)` on the main thread, as a future; None if the main thread
    is not lent (or is the caller: it cannot serve itself)."""
    jobs = _jobs
    if jobs is None or threading.current_thread() is threading.main_thread():
        return None
    future: concurrent.futures.Future = concurrent.futures.Future()
    jobs.put((future, fn, args))
    return future
