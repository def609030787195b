"""The one bounded pool of host threads that ingest's independent units run
on: the part files of a read, the columns of the frame assembled from them
(a column's parts joined, its Arrow-typed conversion, ``inferSchema``'s look
at it, its sanitization), the columns of a table being built (a string
column's encode, every column's conversion to the device dtypes, its padding
and its ``device_put``), the buckets of one long column, the columns of a
table being fetched.  Almost all of a unit's seconds are in Arrow's C kernels
and numpy's loops, which release the GIL, so units on threads overlap.

One pool a process, sized once from the CPUs the process may run on
(``parallel.scheduler.available_cpus``, at most 16), the calling thread
counted: a call's units are claimed in their order by the calling thread and
by as many pool threads as are free, and the results come back in that order.
Because the caller works too, and waits at the end only for units that a
thread is already running, a unit may itself hand units to the pool (a column
whose buckets are encoded side by side) with any number of threads, one
included, and never waits on a queue.  Every caller shares the pool (the main
thread, scheduler nodes, ``prefetch.DecodePool`` workers): no more than its
threads and the callers themselves run ingest at a time.
"""

from __future__ import annotations

import functools
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, NamedTuple, Optional, Sequence

__all__ = ["HostPool", "UnitsRun", "get_host_pool", "record_units"]

_MAX_THREADS = 16


class UnitsRun(NamedTuple):
    results: list  # one a unit, in the units' order
    workers: int   # threads that ran a unit, the calling one among them; 0 where they ran inline
    wall_s: float  # first start to last end of the units


class HostPool:
    """``threads`` threads side by side, the calling thread one of them."""

    def __init__(self, threads: int):
        self.threads = max(1, int(threads))
        self._executor = ThreadPoolExecutor(
            max_workers=self.threads - 1, thread_name_prefix="anovos-host") if self.threads > 1 else None

    def run(self, fn: Callable, items: Sequence, side_by_side: bool = True) -> UnitsRun:
        """``fn(item)`` for every item, results in the items' order.  Side by
        side, the units are claimed in order by this thread and by up to
        ``threads - 1`` pool threads; otherwise (or with one thread, or one
        item) they run here one after the other, as a loop would.  A unit
        that raises stops further units from starting; the error of the
        first such unit in the items' order is raised once the units
        already running have ended.  A span that a unit opens on a pool
        thread has the parent it would have had on this thread
        (``Tracer.under``), and a transfer it books lands on the scheduler
        node this thread runs (``devprof.under``)."""
        call = _Call(fn, items)
        helpers = min(len(items), self.threads) - 1 if side_by_side else 0
        for _ in range(helpers):
            self._executor.submit(call.drain)
        call.drain()
        results = call.finish()
        if call.errors:
            raise call.errors[min(call.errors)]
        return UnitsRun(results, len(call.ran_on) if helpers > 0 else 0,
                        max(call.last_end - call.first_start, 0.0))


class _Call:
    """One :meth:`HostPool.run`: the units and who has claimed which."""

    def __init__(self, fn: Callable, items: Sequence):
        from anovos_tpu.obs import devprof
        from anovos_tpu.obs.tracing import get_tracer

        self.fn, self.items = fn, items
        self.results: list = [None] * len(items)
        self._tracer = get_tracer()
        self._spans = self._tracer.open_spans()  # the calling thread's
        self._under_frame = functools.partial(devprof.under, devprof.current_frame())  # and its node's
        self.errors: Dict[int, BaseException] = {}
        self.ran_on: set = set()
        self.first_start = float("inf")
        self.last_end = 0.0
        self._next = 0
        self._running = 0
        self._cv = threading.Condition()

    def drain(self) -> None:
        """Claim and run units until none is left or one has failed."""
        with self._tracer.under(self._spans), self._under_frame():
            while True:
                with self._cv:
                    if self.errors or self._next == len(self.items):
                        return
                    i = self._next
                    self._next += 1
                    self._running += 1
                error: Optional[BaseException] = None
                t0 = time.perf_counter()
                try:
                    self.results[i] = self.fn(self.items[i])
                except BaseException as e:  # raised by run(), in the items' order
                    error = e
                t1 = time.perf_counter()
                with self._cv:
                    if error is not None:
                        self.errors[i] = error
                    self.ran_on.add(threading.get_ident())
                    self.first_start = min(self.first_start, t0)
                    self.last_end = max(self.last_end, t1)
                    self._running -= 1
                    self._cv.notify_all()

    def finish(self) -> list:
        """The results, once every claimed unit has ended (each is on a
        running thread).  The call lets go of the units and of what they
        returned: a ``drain`` still queued behind other work finds nothing
        to claim, and must not keep a frame alive until it is taken up."""
        with self._cv:
            while self._running:
                self._cv.wait()
            self._next = 0
            results, self.results, self.items, self.fn = self.results, [], (), None
        return results


_POOL: Optional[HostPool] = None
_POOL_LOCK = threading.Lock()


def get_host_pool() -> HostPool:
    """The process's pool, made on first use."""
    global _POOL
    with _POOL_LOCK:
        if _POOL is None:
            from anovos_tpu.parallel.scheduler import available_cpus

            _POOL = HostPool(min(available_cpus(), _MAX_THREADS))
        return _POOL


def record_units(what: str, ran: UnitsRun, **counts) -> None:
    """``<what>_workers`` and ``<what>_wall_s`` of one call's units on the
    row of the pass's tree that the units' spans are filed under (inside
    ``read_dataset``: ``io:read_dataset``), so that the sum of those spans
    over the wall is the overlap the call got, and beside them whatever else
    the caller counted of the units, each as ``<what>_<count>``.  Outside a
    pass: nothing."""
    from anovos_tpu.obs.tracing import get_tracer

    row = get_tracer().tree_row()
    if row is not None:
        row.add(**{f"{what}_workers": ran.workers, f"{what}_wall_s": ran.wall_s},
                **{f"{what}_{name}": count for name, count in counts.items()})
