"""The process's main thread, lent (`util/main_thread.py`)."""

import threading
import time

import pytest

from ray_tpu.util import main_thread


def submit(fn, *args):
    """From another thread, once the main thread serves."""
    while main_thread._jobs is None:
        time.sleep(0.001)
    return main_thread.submit(fn, *args)


def test_jobs_run_on_the_serving_main_thread_and_answer_through_futures():
    got = {}

    def other():
        ran = submit(lambda a, b: (threading.current_thread(), a + b), 2, 3)
        got["value"] = ran.result(timeout=10)
        boom = submit(lambda: 1 / 0)
        got["error"] = boom.exception(timeout=10)

    th = threading.Thread(target=other)
    th.start()
    main_thread.serve(lambda: not th.is_alive(), poll_s=0.01)
    th.join()
    assert got["value"] == (threading.main_thread(), 5)
    assert isinstance(got["error"], ZeroDivisionError)


def test_nothing_is_lent_unless_the_main_thread_serves():
    assert main_thread.submit(print) is None           # nobody serves
    got = []
    th = threading.Thread(target=lambda: got.append(main_thread.submit(print)))
    th.start()
    th.join()
    assert got == [None]


def test_the_main_thread_cannot_serve_itself_and_only_it_can_serve():
    tried = []

    def job():   # runs on the main thread, while it serves
        tried.append(main_thread.submit(print))

    th = threading.Thread(target=lambda: submit(job).result(timeout=10))
    th.start()
    main_thread.serve(lambda: not th.is_alive(), poll_s=0.01)
    assert tried == [None]
    errors = []

    def serve_elsewhere():
        try:
            main_thread.serve(lambda: True)
        except RuntimeError as e:
            errors.append(e)

    th = threading.Thread(target=serve_elsewhere)
    th.start()
    th.join()
    assert len(errors) == 1


def test_what_is_still_queued_when_the_main_thread_leaves_is_cancelled():
    futures, gate = [], threading.Event()

    def other():
        futures.append(submit(time.sleep, 0.2))
        futures.append(submit(print, "never"))
        gate.set()

    th = threading.Thread(target=other)
    th.start()
    # `until` first holds while the first job runs; the second is left
    main_thread.serve(gate.is_set, poll_s=0.01)
    th.join()
    assert futures[0].done() and not futures[0].cancelled()
    assert futures[1].cancelled()
    with pytest.raises(Exception):
        futures[1].result(timeout=1)
    assert main_thread.submit(print) is None
