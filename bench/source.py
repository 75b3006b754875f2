"""The benchmark's arrival source and its completion stamps.

``SpadeService.run(stream)`` reads a stream tick by tick through slices
of ``stream.inc_src/inc_dst/inc_amt/inc_time``.  :class:`Arrivals` stands
behind those four arrays: a read returns only once the last edge it asks
for has been created on the cell's schedule, so the system reads a tick
when its edges have arrived, as a consumer reads a topic, and the
schedule never slows down when the system does (an open loop).

Completion is stamped on the device.  Each read that asks for new edges
enqueues a barrier, a tiny program on the same device, from the reading
thread, so that it lands after everything that thread dispatched before
the read; a waiter thread blocks on it and stamps the time.  The edges
of read ``r`` are therefore complete at the stamp of read ``r + 1``, or
of the barrier :meth:`Arrivals.finish` enqueues when ``run()`` returns.
The stamp holds whether or not the program blocks on each tick, and the
reading thread never blocks on a barrier.
"""

from __future__ import annotations

import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Barriers", "Arrivals"]


def _barrier(x):
    return x + 1


class Barriers:
    """Device barriers, enqueued by the caller and waited on by a thread."""

    def __init__(self, device: jax.Device):
        self._fn = jax.jit(_barrier)
        self._token = jax.device_put(jnp.int32(0), device)
        self._queue: queue.Queue = queue.Queue()
        self.done: dict[object, float] = {}
        self._cond = threading.Condition()
        self._thread = threading.Thread(target=self._wait, name="barriers",
                                        daemon=True)
        self._thread.start()

    def enqueue(self, key) -> None:
        self._queue.put((key, self._fn(self._token)))

    def _wait(self) -> None:
        while (item := self._queue.get()) is not None:
            key, out = item
            with jax.profiler.TraceAnnotation("barrier"):
                out.block_until_ready()
            with self._cond:
                self.done[key] = time.perf_counter()
                self._cond.notify_all()

    def stamp(self, key, timeout: float = 600.0) -> float:
        """The completion time of barrier ``key``, waiting for it."""
        with self._cond:
            if not self._cond.wait_for(lambda: key in self.done, timeout):
                raise TimeoutError(f"barrier {key!r} never completed")
            return self.done[key]

    def close(self) -> None:
        self._queue.put(None)
        self._thread.join(timeout=600.0)
        if self._thread.is_alive():
            raise RuntimeError("the barrier thread did not stop")


class _Column:
    """One streamed array of a ``TxStream``; reads wait for arrival."""

    def __init__(self, source: "Arrivals", values: np.ndarray):
        self._source = source
        self._values = values
        self.shape = values.shape
        self.dtype = values.dtype

    def __len__(self) -> int:
        return self.shape[0]

    def __getitem__(self, key):
        if isinstance(key, slice):
            rng = range(*key.indices(self.shape[0]))
            hi = max(rng[0], rng[-1]) + 1 if len(rng) else 0
        else:
            idx = np.arange(self.shape[0])[key]
            hi = int(np.max(idx)) + 1 if np.size(idx) else 0
        if hi:
            self._source.read(hi)
        return self._values[key]

    def __array__(self, dtype=None, copy=None):
        self._source.read(self.shape[0])
        return np.asarray(self._values, dtype=dtype)


class Arrivals:
    """The streamed edges of one ``run()`` and their schedule.

    ``due[e]`` is when edge ``e`` is created, in seconds after the origin;
    ``window_start`` is the first edge of the measured window.  The origin
    is the host time of the first read of a window edge, and the window
    opens when the barrier of that read completes, so whatever the device
    still runs from set-up stays out of it.  Edges before the window are
    due at once.
    """

    def __init__(self, barriers: Barriers, due: np.ndarray,
                 window_start: int, name: str = "run", on_open=None):
        self.barriers = barriers
        self.name = name
        self._on_open = on_open
        self.due = np.asarray(due, np.float64)
        self.window_start = int(window_start)
        self.covered = 0  # edges handed out so far
        self.bounds: list[tuple[int, int]] = []  # edges of each read
        self.read_at: list[float] = []  # host time of each read
        self.waited = 0.0  # seconds the reading thread waited for arrivals
        self.origin: float | None = None
        self.open_read: int | None = None
        self._window_span = None
        self._finished = False

    def columns(self, *arrays: np.ndarray) -> list[_Column]:
        return [_Column(self, np.asarray(a)) for a in arrays]

    def read(self, hi: int) -> None:
        """Hand out the edges below ``hi`` once they have arrived."""
        if hi <= self.covered:
            return
        r = len(self.bounds)
        now = time.perf_counter()
        self.barriers.enqueue((self.name, r))
        if self.origin is None and hi > self.window_start:
            if self._on_open is not None:
                self._on_open()
            self.origin = now
            self.open_read = r
            self._window_span = jax.profiler.TraceAnnotation("window")
            self._window_span.__enter__()
        self.bounds.append((self.covered, hi))
        self.read_at.append(now)
        self.covered = hi
        if self.origin is not None:
            wake = self.origin + self.due[hi - 1]
            if wake > now:
                with jax.profiler.TraceAnnotation("source_wait"):
                    while (left := wake - time.perf_counter()) > 0:
                        time.sleep(left)
                self.waited += time.perf_counter() - now

    def finish(self) -> float:
        """Stamp ``run()``'s return; returns the window's close."""
        if self._finished:
            raise RuntimeError("finish() called twice")
        self._finished = True
        self.barriers.enqueue((self.name, len(self.bounds)))
        close = self.barriers.stamp((self.name, len(self.bounds)))
        if self._window_span is not None:
            self._window_span.__exit__(None, None, None)
        return close

    # -- what the stamps say ---------------------------------------------

    def completed(self) -> np.ndarray:
        """Per read, when its edges were complete."""
        return np.array([self.barriers.stamp((self.name, r + 1))
                         for r in range(len(self.bounds))])

    def window_open(self) -> float:
        if self.open_read is None:
            raise RuntimeError("no window edge was read")
        return self.barriers.stamp((self.name, self.open_read))

    def latencies(self) -> np.ndarray:
        """Seconds from creation to completion of every window edge."""
        done = self.completed()
        out = []
        for (lo, hi), t in zip(self.bounds, done):
            lo = max(lo, self.window_start)
            if hi > lo:
                out.append(t - (self.origin + self.due[lo:hi]))
        return np.concatenate(out) if out else np.zeros(0)

    def read_lag(self) -> np.ndarray:
        """Per window read, how long after its last edge arrived it was
        read: the queue ahead of the system, which grows when the offered
        rate is above what it sustains."""
        return np.array([
            self.read_at[r] - (self.origin + self.due[hi - 1])
            for r, (lo, hi) in enumerate(self.bounds)
            if hi > self.window_start
        ])
