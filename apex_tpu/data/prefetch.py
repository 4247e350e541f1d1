"""Device-transfer prefetch: overlap host->device copies with compute.

Behavioral spec: the reference's ``data_prefetcher``
(``examples/imagenet/main_amp.py:256-276``) — batch N+1's H2D copy runs
on a side CUDA stream while the model computes on batch N, so the copy
never sits on the step's critical path.

The TPU redesign needs no stream machinery: ``jax.device_put`` is
*asynchronous* — it returns immediately with arrays whose transfers are
in flight, and any computation consuming them is sequenced after the
copy by the runtime.  What DOES sit on the critical path is the *host*
side of ``next(source)`` — decode/gather time the old single-queue
design paid inside the consumer's ``__next__``.  The double-buffered
form runs a dedicated transfer thread: it pulls host batches from the
source and issues their ``device_put``/``dp_shard_batch`` into a bounded
queue, so while step N computes, batch N+1's transfer is already in
flight *and* the source's own decode pool is filling batch N+2 — the
three pipeline layers (decode, H2D, compute) overlap pairwise, and the
consumer only blocks when ALL of them fall behind.

That residual block is the **stall** — the one number that says whether
the input pipeline feeds the chip.  Every ``__next__`` records it:
``data/stall_ms`` gauge (last step) and ``span_ms/data/next_wait``
histogram in the default :class:`~apex_tpu.observability.metrics.
MetricRegistry`, under a ``jax.profiler.TraceAnnotation`` so captured
traces show the wait as a range (docs/observability.md catalog).

Composition contract (enforced): wrap a **loader** (``ImageFolderLoader``
/ ``PackedLoader`` / ``PackedSequenceLoader`` / ``DataService``) directly
— nothing in between — and checkpoint the *wrapper's*
``consumed_samples``.  Wrapping another :class:`DevicePrefetcher` (or any
wrapper without the loader resume surface) raises immediately rather
than mis-counting ``local_batch * dp`` from the wrong layer.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterable, Optional

__all__ = ["DevicePrefetcher", "prefetch_to_device"]


class _End:
    """Exhaustion sentinel — distinct from any source item, so a source
    legitimately yielding ``None`` is delivered, not dropped (the old
    ``next(it, None)`` conflation)."""


class _Error:
    """Exception relay from the transfer thread to the consumer."""

    def __init__(self, exc: BaseException):
        self.exc = exc


class DevicePrefetcher:
    """Iterator over device-placed batches; see :func:`prefetch_to_device`.

    ``consumed_samples`` is the checkpoint-correct resume point: samples
    in batches already **delivered to the caller** — tracked directly as
    ``consumed_at_construction + delivered_batches * (local_batch * dp)``
    so a concurrent transfer thread can never skew it (the source's own
    count runs ahead by the in-flight window).  Checkpoint this wrapper's
    value, not the loader's, and re-wrap a fresh loader from it after
    restore.

    Resource contract: ``close()`` (or the context manager) stops the
    transfer thread, closes the source iterator, **closes the source
    loader** (pass-through — the decode pool does not live until
    ``__del__``), and rewinds the source's samplers past any batches
    pulled but never delivered (``rewind_batches``), so after ``close()``
    the source's ``consumed_samples`` agrees with the wrapper's.
    """

    def __init__(self, source, place: Optional[Callable], depth: int,
                 mesh=None, registry=None):
        if isinstance(source, DevicePrefetcher):
            raise TypeError(
                "prefetch_to_device(prefetch_to_device(...)): nested "
                "device prefetchers are unsupported — the wrapper reads "
                "local_batch/dp from its source for resume bookkeeping, "
                "which a second wrapper layer would mis-count.  Compose "
                "as loader -> prefetch_to_device, nothing in between.")
        self._source = source
        self._it = iter(source)
        self._place = place  # None: resolved lazily at first batch
        self._mesh = mesh
        self._depth = max(0, depth)
        self._registry = registry
        self._lock = threading.Lock()
        self._delivered = 0   # batches handed to the caller
        self._pulled = 0      # batches taken from the source iterator
        self._consumed0 = getattr(source, "consumed_samples", None)
        self._queue: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._exhausted = False
        self._closed = False

    # -- resume bookkeeping -------------------------------------------

    def _per_batch(self) -> int:
        try:
            return self._source.local_batch * self._source.dp
        except AttributeError:
            raise AttributeError(
                "the wrapped source has no local_batch/dp; wrap a loader "
                "(ImageFolderLoader/PackedLoader/PackedSequenceLoader/"
                "DataService) directly — composition order is "
                "loader -> prefetch_to_device, nothing in between") \
                from None

    @property
    def in_flight(self) -> int:
        """Batches pulled from the source but not yet delivered
        (queued on device or mid-placement).  When the source exposes
        ``consumed_samples``, derived as
        ``(source.consumed - wrapper.consumed) / per_batch`` — the
        source's count is updated inside its own yield, so deriving from
        it (rather than the wrapper's ``_pulled``, incremented a moment
        later) keeps ``source == wrapper + in_flight`` an identity at
        any instant, and survives a close() whose thread join timed
        out."""
        src = getattr(self._source, "consumed_samples", None)
        if src is not None:
            try:
                per = self._per_batch()
            except AttributeError:
                per = None
            if per:
                with self._lock:
                    mine = self._consumed0 + self._delivered * per
                return max(0, (src - mine) // per)
        with self._lock:
            return self._pulled - self._delivered

    @property
    def consumed_samples(self) -> int:
        if self._consumed0 is None:
            raise AttributeError(
                "the wrapped source has no consumed_samples; wrap a "
                "loader (not a plain iterator) for resume bookkeeping — "
                "composition order is loader -> prefetch_to_device, "
                "nothing in between")
        with self._lock:
            return self._consumed0 + self._delivered * self._per_batch()

    # -- placement -----------------------------------------------------

    def _resolve_place(self) -> Callable:
        # Deferred to first use so `prefetch_to_device(it)` constructed
        # *before* initialize_model_parallel() still picks up dp sharding
        # once iteration starts.
        import jax

        from apex_tpu.parallel import distributed as dist
        from apex_tpu.parallel import mesh as mesh_lib

        if (self._mesh is not None
                or mesh_lib.model_parallel_is_initialized()):
            mesh = self._mesh
            return lambda b: dist.dp_shard_batch(b, mesh)
        return jax.device_put

    # -- transfer thread ----------------------------------------------

    def _pull_and_place(self):
        """One source pull + device placement; returns the queue item."""
        try:
            item = next(self._it)
        except StopIteration:
            return _End()
        except BaseException as e:  # noqa: BLE001 — relayed, not eaten
            return _Error(e)
        with self._lock:
            self._pulled += 1
        try:
            return self._place(item)
        except BaseException as e:  # noqa: BLE001
            return _Error(e)

    def _run(self) -> None:
        while not self._stop.is_set():
            out = self._pull_and_place()
            final = isinstance(out, (_End, _Error))
            while not self._stop.is_set():
                try:
                    self._queue.put(out, timeout=0.2)
                    break
                except queue.Full:
                    continue
            if final:
                return

    # -- iterator ------------------------------------------------------

    def __iter__(self) -> "DevicePrefetcher":
        return self

    def __next__(self):
        if self._exhausted or self._closed:
            raise StopIteration
        if self._place is None:
            self._place = self._resolve_place()
        if self._depth == 0:
            # degenerate synchronous mode: map(place, source)
            out = self._pull_and_place()
        else:
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name="apex-device-prefetch",
                    daemon=True)
                self._thread.start()
            out = self._get_with_stall()
        if isinstance(out, _End):
            self._exhausted = True
            raise StopIteration
        if isinstance(out, _Error):
            self._exhausted = True
            raise out.exc
        with self._lock:
            self._delivered += 1
        return out

    def _get_with_stall(self):
        """Blocking queue pop, measured: the time the consumer waits here
        is the pipeline's *stall* — the step-time cost of the input path
        after every overlap has done its work.  Poll-with-timeout rather
        than a bare blocking get (the ProducerLoader._finish discipline):
        a concurrent ``close()`` from a watchdog/preemption thread must
        wake a consumer already parked here, not leave it blocked
        forever on a queue nobody will fill."""
        import jax

        if self._registry is None:
            from apex_tpu.observability.metrics import default_registry

            self._registry = default_registry()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("apex/data/next_wait"):
            while True:
                try:
                    out = self._queue.get(timeout=0.5)
                    break
                except queue.Empty:
                    if self._stop.is_set():
                        out = _End()
                        break
        stall_ms = (time.perf_counter() - t0) * 1e3
        self._registry.gauge("data/stall_ms").set(stall_ms)
        self._registry.histogram("span_ms/data/next_wait").observe(stall_ms)
        # Flight recorder (no-op unless armed): the same blocking wait,
        # as a timeline interval feeding the goodput ``data_stall``
        # bucket (docs/observability.md) — this is main-thread time
        # outside any step scope, so attribution stays disjoint.
        from apex_tpu.observability import timeline

        timeline.emit("data_stall", dur_s=stall_ms / 1e3)
        return out

    # -- shutdown ------------------------------------------------------

    def close(self, *, close_source: bool = True) -> None:
        """Stop the transfer thread, close the source iterator, rewind
        the source's samplers past undelivered in-flight batches (so its
        ``consumed_samples`` matches the wrapper's), and — the resource
        pass-through — close the source loader itself, releasing its
        decode pool.  Idempotent.

        ``close_source=False`` leaves the loader open (the multi-epoch
        loop shape: re-wrap the same loader for the next epoch)."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        if self._thread is not None:
            # unblock a producer waiting on a full queue
            try:
                while True:
                    self._queue.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=5.0)
            self._thread = None
        # generator sources (the loaders' __iter__) rewind their OWN
        # prefetch window in their finally block when closed.  Guard:
        # for self-iterating sources (DataService, plain iterators with
        # close()), iter(source) IS the source — closing "the iterator"
        # there would close the source even under close_source=False.
        if self._it is not self._source:
            it_close = getattr(self._it, "close", None)
            if callable(it_close):
                try:
                    it_close()
                except Exception:
                    pass  # a producer stuck past the join timeout
        undelivered = self.in_flight
        rewind = getattr(self._source, "rewind_batches", None)
        if undelivered and callable(rewind):
            rewind(undelivered)
            with self._lock:
                self._pulled -= undelivered
        if close_source:
            src_close = getattr(self._source, "close", None)
            if callable(src_close):
                src_close()

    def __enter__(self) -> "DevicePrefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):  # best-effort backstop
        # does NOT close the source: a dropped (e.g. exhausted) wrapper
        # must not yank the decode pool out from under a loader the
        # caller re-wrapped for the next epoch — only an explicit
        # close() passes through
        try:
            self.close(close_source=False)
        except Exception:
            pass


def prefetch_to_device(iterator: Iterable, mesh=None, depth: int = 2,
                       place: Optional[Callable] = None,
                       registry=None) -> DevicePrefetcher:
    """Yield batches from ``iterator`` already placed on device, with a
    dedicated transfer thread keeping up to ``depth`` placed batches
    queued ahead of the consumer.

    ``place`` maps a host batch to device arrays; the default shards the
    leading dim over the data-parallel axes via
    :func:`apex_tpu.parallel.dp_shard_batch` when a ``mesh`` is given
    (or one is initialized), else a plain ``jax.device_put``.

    ``depth=0`` degenerates to ``map(place, iterator)`` (no thread).
    For exact mid-epoch resume, checkpoint the returned wrapper's
    ``consumed_samples`` (NOT the loader's own, which runs ahead by the
    in-flight window) and rebuild loader + wrapper from it after
    restore.  Composition order is enforced: wrap a loader directly —
    nesting two device prefetchers raises ``TypeError``.

    The default placement is resolved at *first iteration*, not at
    construction, so wrapping before ``initialize_model_parallel()``
    still shards over the mesh that exists when batches start flowing.

    Observability: each ``__next__`` records its blocking wait into the
    ``data/stall_ms`` gauge and the ``span_ms/data/next_wait`` histogram
    of ``registry`` (default: the process registry); no benchmark cell
    runs an input pipeline yet (ROADMAP W9).
    """
    return DevicePrefetcher(iterator, place, depth, mesh=mesh,
                            registry=registry)
