"""Async host→device batch prefetch.

Two overlaps, two mechanisms:

- **Transfer overlap** — ``jax.device_put`` is asynchronous: issuing the
  transfer for batch k+1 while batch k's step runs hides the PCIe/ICI
  copy behind compute (the reference relies on MXNet's threaded DataIter
  + engine for the same overlap).  Keeping ``depth`` batches in flight
  bounds device memory.
- **Host-work overlap** — a plain generator pipeline still runs the host
  loader (decode, augment, letterbox, ``np.stack``) *synchronously in
  the consumer's thread* between steps: the device sits idle for exactly
  the loader's per-batch CPU time.  ``_HostPrefetcher`` moves the
  ``next(it)`` calls to a background thread with a bounded handoff queue
  (``host_depth`` batches read ahead — the one-step double buffer), so
  loader time overlaps device time instead of serializing with it.

Batch ORDER is unchanged by both (single producer, single consumer,
FIFO queue), so schedule determinism — quarantine substitution, chaos
bit-exact resume — is preserved.

:class:`PrefetchStats` measures what the overlap does NOT hide, in the
consumer's thread: ``stall_s``, the time blocked waiting for a batch that
is not ready (the queue ran dry — the loader is slower than the step), and
``put_s``, the time inside the ``device_put`` of the next batch, which this
generator issues synchronously between two steps.  The same two stretches
go on the program's timeline as ``feed.wait`` and ``feed.put`` spans
(subsystem ``train``, ``seq`` = index of the batch = the step that consumes
it).  ``train()`` logs them as ``data_stall_ms`` / ``data_put_ms``; the
benchmark reads them as ``data_stall_ms.train`` / ``feed_put_ms.train``.
"""

from __future__ import annotations

import collections
import logging
import queue
import threading
from typing import Iterator, Optional

import jax

from mx_rcnn_tpu import obs
from mx_rcnn_tpu.parallel.mesh import shard_batch

log = logging.getLogger("mx_rcnn_tpu")


class PrefetchStats:
    """Consumer-side stall accounting for the host-prefetch stage.

    ``stall_s`` accumulates ONLY time the consumer spends blocked waiting
    for the producer (an empty handoff queue); a batch that is already
    buffered costs ~0 regardless of how long the loader took to build it
    — that work was hidden behind the device step, which is the point.
    ``put_s`` accumulates the consumer's time inside ``device_put``.
    ``take()`` returns-and-resets stall and batches, ``take_put()`` the
    put seconds, so callers meter per interval (the training loop) or per
    timed window (bench) without seeding from a wall clock.
    """

    def __init__(self) -> None:
        self.stall_s = 0.0
        self.batches = 0
        self.put_s = 0.0
        # The span the consumer is inside while it pulls (train()'s
        # ``data``): the feed's spans become its children.  None = roots.
        self.parent: Optional[obs.Span] = None

    def add(self, stall_s: float) -> None:
        self.stall_s += stall_s
        self.batches += 1

    def add_put(self, put_s: float) -> None:
        self.put_s += put_s

    def take(self) -> tuple[float, int]:
        """(accumulated stall seconds, batches) since the last take."""
        out = (self.stall_s, self.batches)
        self.stall_s = 0.0
        self.batches = 0
        return out

    def take_put(self) -> float:
        """Seconds inside device_put since the last take_put."""
        out = self.put_s
        self.put_s = 0.0
        return out


def _feed_span(stats: Optional[PrefetchStats], name: str, seq: int):
    parent = stats.parent if stats is not None else None
    if parent is not None:
        return parent.child(name, attrs={"seq": seq})
    return obs.span(name, subsystem="train", attrs={"seq": seq})


class _HostPrefetcher:
    """Background-thread stage: pulls from ``it`` ahead of the consumer.

    Exceptions raised by the source iterator are re-raised in the
    consumer at the position they occurred (the failure is part of the
    stream, not swallowed in the thread).  ``close()`` stops the thread
    promptly even if it is blocked on a full queue; iterating a closed
    prefetcher raises StopIteration.
    """

    _DONE = object()

    def __init__(
        self, it: Iterator, depth: int = 1,
        stats: Optional[PrefetchStats] = None,
    ):
        self._it = it
        self._q: queue.Queue = queue.Queue(maxsize=max(depth, 1))
        self._stop = threading.Event()
        self._stats = stats
        self._seq = 0  # index of the next batch the consumer pulls
        self._thread = threading.Thread(
            target=self._run, args=(it,), name="host-prefetch", daemon=True
        )
        self._thread.start()

    def _run(self, it: Iterator) -> None:
        try:
            for item in it:
                while not self._stop.is_set():
                    try:
                        self._q.put((item, None), timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if self._stop.is_set():
                    return
            payload = (self._DONE, None)
        except BaseException as exc:  # noqa: BLE001 — relayed to consumer
            payload = (self._DONE, exc)
        while not self._stop.is_set():
            try:
                self._q.put(payload, timeout=0.1)
                return
            except queue.Full:
                continue

    def __iter__(self) -> "_HostPrefetcher":
        return self

    def __next__(self):
        if self._stop.is_set():
            raise StopIteration
        # Time ONLY the blocking wait (``feed.wait``): a non-empty queue
        # short-circuits through get_nowait with no clock read and no span.
        stats = self._stats
        try:
            item, exc = self._q.get_nowait()
            stall_s = 0.0
        except queue.Empty:
            with _feed_span(stats, "feed.wait", self._seq) as sp:
                item, exc = self._q.get()
            stall_s = sp.dur_ns / 1e9
        self._seq += 1
        if stats is not None:
            stats.add(stall_s)
        if item is self._DONE:
            self._stop.set()
            if exc is not None:
                raise exc
            raise StopIteration
        return item

    def close(
        self, raise_pending: bool = False
    ) -> Optional[BaseException]:
        """Stop and join the thread, close the source iterator, and
        surface any exception the producer hit that the consumer never
        pulled (it would otherwise vanish with the thread).  Returns the
        pending exception (or re-raises it with ``raise_pending``) so
        callers choose: the training loop logs it at teardown, the
        loader-side wrapper propagates it."""
        self._stop.set()
        pending: Optional[BaseException] = None

        def drain() -> None:
            nonlocal pending
            try:
                while True:
                    _, exc = self._q.get_nowait()
                    if exc is not None and pending is None:
                        pending = exc
            except queue.Empty:
                pass

        # Drain so a producer blocked on put() observes the stop event,
        # join, then drain again for anything it published while exiting.
        drain()
        self._thread.join(timeout=5.0)
        drain()
        # Close the source chain (generators propagate close to theirs) so
        # loader prefetch threads and input-service workers are reclaimed,
        # not leaked behind a dead consumer.  A pending exception the
        # source surfaces AT close (the loader's own prefetch wrapper does
        # this) folds into ours — teardown itself must not die on it.
        close = getattr(self._it, "close", None)
        if close is not None:
            try:
                close()
            except RuntimeError:
                pass  # generator already executing/closed
            except BaseException as exc:  # noqa: BLE001 — folded, not fatal
                if pending is None:
                    pending = exc
        if pending is not None and raise_pending:
            raise pending
        return pending


def _timed_pulls(it: Iterator, stats: PrefetchStats) -> Iterator:
    """host_depth=0 fallback: every pull is synchronous, so the whole
    ``next(it)`` is consumer-blocking stall (``feed.wait``) by definition."""
    seq = 0
    while True:
        sp = _feed_span(stats, "feed.wait", seq)
        try:
            item = next(it)
        except StopIteration:
            return
        sp.end()
        stats.add(sp.dur_ns / 1e9)
        seq += 1
        yield item


def device_prefetch(
    it: Iterator, mesh: Optional[jax.sharding.Mesh], depth: int = 2,
    spatial: bool = False, stacked: bool = False, host_depth: int = 1,
    stats: Optional[PrefetchStats] = None,
) -> Iterator:
    """Wrap a host batch iterator: batches come out device-resident (sharded
    over the mesh when given), ``depth`` transfers ahead of consumption.
    ``stacked``: batches carry a leading steps-per-call axis (K, B, ...).
    ``host_depth``: batches the background host-prefetch thread reads
    ahead of the device_put stage (0 = synchronous pulls in the consumer
    thread — the pre-r6 behavior, kept for strictly single-threaded
    debugging).  ``stats``: optional :class:`PrefetchStats` accumulating
    the consumer-side stall (data-starvation) and ``device_put`` time.
    Closing the returned generator (``gen.close()``) stops the thread."""
    q: collections.deque = collections.deque()
    if host_depth <= 0:
        src: Iterator = it if stats is None else _timed_pulls(it, stats)
    else:
        src = _HostPrefetcher(it, host_depth, stats=stats)

    def put(batch, seq):
        # Issued in the consumer's thread, between two steps: the copy
        # itself is asynchronous, the host's part of it (layout, staging)
        # is not, and the device may idle for it (``feed.put``).
        with _feed_span(stats, "feed.put", seq) as sp:
            if mesh is not None:
                out = shard_batch(
                    batch, mesh, spatial=spatial, stacked=stacked
                )
            else:
                out = jax.device_put(batch)
        if stats is not None:
            stats.add_put(sp.dur_ns / 1e9)
        return out

    try:
        for seq, batch in enumerate(src):
            q.append(put(batch, seq))
            if len(q) > depth:
                yield q.popleft()
        while q:
            yield q.popleft()
    finally:
        if isinstance(src, _HostPrefetcher):
            pending = src.close()
            if pending is not None:
                # The consumer stopped before it would have seen this (a
                # loader failure mid-read-ahead during early close).  Log
                # rather than raise: teardown paths (rollback, shutdown)
                # must not die on a stream the run already abandoned.
                log.warning(
                    "host prefetch: source raised after consumer stopped: "
                    "%s: %s", type(pending).__name__, pending,
                )
        else:
            close = getattr(it, "close", None)
            if close is not None:
                close()
