"""Host spans the benchmark puts round its calls into the program, and the
profiler's trace of a window.

Spans are kept in memory (name, start, end on ``time.perf_counter``); while
a trace is being taken each is also written into the profiler's own trace
as a ``TraceAnnotation``, so that the reduction can say what the host was
doing in a gap of the device.
"""

import contextlib
import glob
import os
import shutil
import time

from lib import xplane

SPANS = []          # (name, start, end)
_tracing = False


@contextlib.contextmanager
def span(name: str):
    if _tracing:
        import jax

        note = jax.profiler.TraceAnnotation(name)
        note.__enter__()
    t0 = time.perf_counter()
    try:
        yield
    finally:
        SPANS.append((name, t0, time.perf_counter()))
        if _tracing:
            note.__exit__(None, None, None)


def start(cell) -> dict:
    """Start the profiler; its files go under the checkout's cache
    directory and are removed once reduced."""
    global _tracing
    import jax

    directory = os.path.join(cell.scratch, "trace", cell.name)
    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory)
    jax.profiler.start_trace(directory)
    _tracing = True
    note = jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN)
    note.__enter__()
    return {"directory": directory, "window": note,
            "n_devices": len(cell.devices)}


def stop(handle: dict) -> dict:
    """Stop the profiler and reduce what it wrote."""
    global _tracing
    import jax

    handle["window"].__exit__(None, None, None)
    _tracing = False
    jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(handle["directory"],
                                   "plugins/profile/*/*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb, found {paths}")
    reduced = xplane.reduce(paths[0])
    shutil.rmtree(handle["directory"], ignore_errors=True)
    if len(reduced["devices"]) != handle["n_devices"]:
        raise RuntimeError(
            f"trace holds {len(reduced['devices'])} device planes, the cell "
            f"runs on {handle['n_devices']}")
    return reduced
