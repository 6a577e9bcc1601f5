"""What a run leaves for the metric readers (``metrics/<name>.py``).

A reader takes a :class:`Record` and returns a number, or ``None`` where the
run has nothing for it to read (no trace, no device peaks, no requests of
the kind it reads).
"""
from __future__ import annotations

import dataclasses

from harness import work
from harness.drive import Chunk, Req, Step
from harness.tracing import Trace


@dataclasses.dataclass
class Record:
    cell: str
    sizes: dict  # reference.<arch>.sizes(config)
    peak: dict | None  # bench/peaks.json entry of this device kind
    setup_s: float
    t_open: float  # perf_counter seconds
    t_close: float
    requests: list[Req]
    steps: list[Step]
    chunks: list[Chunk]
    gauges: list[tuple[float, float, float]]  # t, pool capacity, live tokens
    counters: dict[str, tuple[float, float]]  # program counter at (open, close)
    compiles_in_window: int
    queue_wait_ms: dict[int, float]  # rid → the program's admission wait
    trace: Trace | None = None

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def in_window(self, t: float | None) -> bool:
        return t is not None and self.t_open <= t < self.t_close

    def due_in_window(self) -> list[Req]:
        return [r for r in self.requests if self.in_window(r.due)]

    def steps_in(self, start: float, end: float) -> list[Step]:
        return [s for s in self.steps if s.ready is not None and start <= s.ready < end]

    def decode_work(self, steps: list[Step]) -> tuple[float, float, float]:
        """(flops, bytes, least seconds) the model needs for ``steps``."""
        flops = nbytes = least = 0.0
        for s in steps:
            f, b = work.decode_step(self.sizes, s.ctxs)
            flops += f
            nbytes += b
            if self.peak is not None:
                least += work.least_time(f, b, self.peak)
        return flops, nbytes, least

    def prefill_flops(self, steps: list[Step]) -> float:
        """FLOPs of the prefill chunks that ran just before ``steps``."""
        idx = {s.index for s in steps}
        return sum(
            work.prefill_chunk(self.sizes, c.t0, c.live, c.final)
            for c in self.chunks
            if c.step in idx
        )
