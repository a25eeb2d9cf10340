"""Parked writer threads: the per-drive shard writers of a PUT run on
daemon threads that outlive the object, instead of on n threads created
and joined for each one.

`Thread.start()` returns only once the new thread has run far enough to
say so: the starter gives the GIL away and has to win it back among every
runnable thread of the server, about 10 ms a start under twenty PUTs, paid
serially on the request's path before its first block is read. Handing a
job to a thread that is already parked is a queue append and a wake-up;
the request thread keeps the GIL.

A writer holds its thread for the whole stream of its PUT (create_file
consumes the per-drive queue to its sentinel), so a job must never queue
behind a running one: the producer's bounded queue would fill and a
healthy drive be stamped timed out. Hence no cap on what runs at once
(which is why the jobs cannot ride metadata._shared_pool); only what
stays parked is bounded, in count and in time.

A thread resolves its job's future before it parks. A PUT that follows at
once may so find the stack empty while n threads are about to park, and
start n more; the surplus sits out IDLE_EXIT_S and leaves.
"""

from __future__ import annotations

import collections
import queue
import threading
from concurrent.futures import Future

from minio_tpu import obs

# A parked thread that gets no job for this long exits. Last parked is
# first reused, so the threads a lighter load no longer needs are the ones
# that sit out the time.
IDLE_EXIT_S = 30.0
# Threads kept parked at most: twenty PUTs on sixteen drives hold 320.
MAX_PARKED = 1024

_JOBS = obs.counter(
    "minio_tpu_shard_writer_jobs_total",
    "Shard-writer jobs handed over by PUT fan-outs (one per drive)").labels()
_REUSED = obs.counter(
    "minio_tpu_shard_writer_reused_total",
    "Shard-writer jobs taken by a parked thread, no thread started").labels()


class ParkedThreads:
    """An unbounded set of daemon threads, each running one job at a time
    and parking between jobs. Not named `mtpu-io…`: run_bounded runs inline
    on threads with that prefix, and the drive wrappers under create_file
    would change behaviour."""

    def __init__(self):
        # Inboxes of the parked threads, a stack. A thread is in it only
        # while it waits for a job; whoever takes an inbox out owes it a
        # job (a submit) or leaves (the thread itself, its time up). No
        # lock: each of append, pop and remove is one atomic call, and a
        # lock's holder may be waiting for the GIL with every PUT's
        # hand-over queued behind it.
        self._parked: collections.deque[queue.SimpleQueue] = (
            collections.deque())

    def submit(self, fn, *args) -> Future:
        """Run fn(*args) on a parked thread, or on a new one when none is
        parked; never behind another job. Returns at once with the job's
        future (fn's exception, if any, is the future's). Pass
        obs.ctx_wrap(fn): a reused thread carries nothing of the request
        that submits."""
        job = (Future(), fn, args)
        _JOBS.inc()
        try:
            inbox = self._parked.pop()
        except IndexError:
            threading.Thread(target=self._work, args=(job,), daemon=True,
                             name="shard-writer").start()
        else:
            _REUSED.inc()
            inbox.put(job)
        return job[0]

    def parked(self) -> int:
        return len(self._parked)

    def _work(self, job) -> None:
        inbox: queue.SimpleQueue = queue.SimpleQueue()
        while True:
            fut, fn, args = job
            try:
                fut.set_result(fn(*args))
            except BaseException as e:  # noqa: BLE001 - handed to the waiter
                fut.set_exception(e)
            # Nothing of the finished request stays referenced while parked.
            del job, fut, fn, args
            if len(self._parked) >= MAX_PARKED:     # a few over at most
                return
            self._parked.append(inbox)
            try:
                job = inbox.get(timeout=IDLE_EXIT_S)
            except queue.Empty:
                try:
                    self._parked.remove(inbox)
                except ValueError:
                    # Taken by a submit as the time ran out: its job
                    # follows.
                    job = inbox.get()
                else:
                    return


_WRITERS = ParkedThreads()


def shard_writers() -> ParkedThreads:
    """The process-wide writer threads of PUT fan-outs."""
    return _WRITERS
