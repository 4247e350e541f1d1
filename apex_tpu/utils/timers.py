"""Wall-clock timers — Megatron ``_Timers`` analog.

Reference: ``apex/transformer/pipeline_parallel/_timers.py:6-83`` — named
timers with ``torch.cuda.synchronize`` on start/stop, ``log`` printing and a
TensorBoard writer hook; accessor ``get_timers``
(``pipeline_parallel/utils.py:146-157``).

TPU version synchronizes via ``jax.block_until_ready`` on a token the caller
passes (or ``jax.effects_barrier``).  The NVTX-range analog
(``apex/parallel/distributed.py:363`` ``nvtx.range_push``) is
``apex_tpu.observability.spans.span``.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import jax

__all__ = ["Timers", "get_timers"]


class _Timer:
    def __init__(self, name: str):
        self.name = name
        self.elapsed_ = 0.0
        self.started_ = False
        self.start_time = 0.0

    def start(self, sync_on: Optional[jax.Array] = None):
        assert not self.started_, f"timer {self.name} already started"
        if sync_on is not None:
            jax.block_until_ready(sync_on)
        self.start_time = time.perf_counter()
        self.started_ = True

    def stop(self, sync_on: Optional[jax.Array] = None):
        assert self.started_, f"timer {self.name} not started"
        if sync_on is not None:
            jax.block_until_ready(sync_on)
        self.elapsed_ += time.perf_counter() - self.start_time
        self.started_ = False

    def reset(self):
        self.elapsed_ = 0.0
        self.started_ = False

    def elapsed(self, reset: bool = True) -> float:
        started = self.started_
        if started:
            self.stop()
        e = self.elapsed_
        if reset:
            self.reset()
        if started:
            self.start()
        return e


class Timers:
    """Group of named timers (``_Timers`` ``_timers.py:40-83``)."""

    def __init__(self):
        self.timers: Dict[str, _Timer] = {}

    def __call__(self, name: str) -> _Timer:
        if name not in self.timers:
            self.timers[name] = _Timer(name)
        return self.timers[name]

    def log(self, names=None, normalizer: float = 1.0, reset: bool = True) -> str:
        names = names if names is not None else list(self.timers)
        parts = [
            f"{n}: {self.timers[n].elapsed(reset=reset) * 1000.0 / normalizer:.2f}ms"
            for n in names
            if n in self.timers
        ]
        line = "time (ms) | " + " | ".join(parts)
        print(line, flush=True)
        return line

    def write(self, names, writer, iteration: int, normalizer: float = 1.0,
              reset: bool = False) -> None:
        """Export timer values (reference ``_Timers.write``
        ``pipeline_parallel/_timers.py:69-77``, which targets a
        TensorBoard ``SummaryWriter``).

        ``writer`` is duck-typed: anything with ``add_scalar(tag, value,
        step)`` (TensorBoard-compatible), or a file path — then one JSON
        line ``{"iteration", "timers": {name: seconds}}`` is appended (no
        TB dependency in this image; the JSONL is trivially convertible).
        """
        values = {n: self.timers[n].elapsed(reset=reset) / normalizer
                  for n in names if n in self.timers}
        if hasattr(writer, "add_scalar"):
            for name, value in values.items():
                writer.add_scalar(f"timers/{name}", value, iteration)
        else:
            import json

            with open(writer, "a") as f:
                f.write(json.dumps({"iteration": iteration,
                                    "timers": values}) + "\n")


_GLOBAL_TIMERS: Optional[Timers] = None


def get_timers() -> Timers:
    """Accessor analog of ``pipeline_parallel/utils.py:146-157``."""
    global _GLOBAL_TIMERS
    if _GLOBAL_TIMERS is None:
        _GLOBAL_TIMERS = Timers()
    return _GLOBAL_TIMERS
