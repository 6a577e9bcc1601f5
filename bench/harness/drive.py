"""Drive the served path (``BatchEngine.submit`` / ``step``) under a mix.

Token readiness is stamped on the device's clock as the host sees it: a
watcher thread blocks on each produced token array in dispatch order (a
request's first token, then each decode step's sampled ``(B,)`` array) and
stamps ``time.perf_counter()`` when it is ready.  The step loop itself never
waits on a result it has just dispatched; it only keeps at most ``LAG``
decode steps in flight beyond the last one ready — a server streams tokens
back to its clients, so it cannot run unboundedly far ahead of them.
"""
from __future__ import annotations

import contextlib
import dataclasses
import queue
import threading
import time

import jax

LAG = 2  # decode steps dispatched beyond the last one ready
DRAIN_S = 60.0  # how long past the close a due answer is waited for
IDLE_POLL_S = 0.002  # the loop's sleep while the engine has nothing to do


@dataclasses.dataclass
class Req:
    rid: int
    index: int
    prompt_len: int
    due: float
    submit: float = 0.0
    client: int = -1
    first: float | None = None  # first token ready
    stamps: list[float] = dataclasses.field(default_factory=list)
    done: bool = False


@dataclasses.dataclass
class Step:
    index: int  # position in the engine's stream of sampled arrays
    ready: float | None
    ctxs: list[int]  # each active sequence's context (incl. this token)


@dataclasses.dataclass
class Chunk:
    rid: int
    t0: int
    live: int
    final: bool
    step: int  # the decode step that follows it on the device


class Watcher:
    """Stamps device arrays as they become ready, in dispatch order."""

    def __init__(self):
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        self._cv = threading.Condition()
        self.stamps: dict[tuple, float] = {}
        self.stream_ready = 0
        self.error: BaseException | None = None
        self._thread = threading.Thread(target=self._run, name="bench-watcher", daemon=True)
        self._thread.start()

    def put(self, key: tuple, array) -> None:
        self._q.put((key, array))

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            key, array = item
            try:
                array.block_until_ready()
            except Exception as e:  # raised again by close(), not lost here
                self.error = e
            t = time.perf_counter()
            with self._cv:
                self.stamps[key] = t
                if key[0] == "s":
                    self.stream_ready = key[1] + 1
                self._cv.notify_all()

    def wait_stream(self, n: int, timeout: float | None = None) -> bool:
        """Block until the first ``n`` stream entries are ready."""
        with self._cv:
            return self._cv.wait_for(lambda: self.stream_ready >= n, timeout)

    def stamp(self, key: tuple) -> float | None:
        with self._cv:
            return self.stamps.get(key)

    def close(self) -> None:
        """Stamp everything queued, then stop the thread."""
        self._q.put(None)
        self._thread.join()
        if self.error is not None:
            raise self.error


def annotate(name: str, on: bool):
    return jax.profiler.TraceAnnotation(name) if on else contextlib.nullcontext()


class Load:
    """One engine under one mix; keeps the record the metrics read."""

    def __init__(self, engine, mix, *, annotations: bool = False):
        self.eng = engine
        self.mix = mix
        self.annotations = annotations
        self.watch = Watcher()
        self.reqs: dict[int, Req] = {}
        self.chunks: list[Chunk] = []
        self.gauges: list[tuple[float, float, float]] = []  # t, capacity, live
        # what the engine served before this load began (the warm set)
        # is neither stamped nor recorded
        self._queued_first: set[int] = set(engine._requests)
        self._queued_stream = len(engine._stream)
        self.watch.stream_ready = self._queued_stream
        self._span_seen = len(engine.obs.tracer.spans)
        self._chunk_width = engine.sched.C

    # ---- the served path -------------------------------------------------
    def submit(self, planned, due: float, client: int = -1) -> Req:
        ids = self.mix.prompt_ids(planned.index, planned.prompt_len)
        with annotate("bench.submit", self.annotations):
            rid = self.eng.submit(ids, planned.output_len)
        req = Req(rid, planned.index, planned.prompt_len, due,
                  submit=time.perf_counter(), client=client)
        self.reqs[rid] = req
        return req

    def step(self) -> bool:
        """One engine step, after bounding how far the host runs ahead."""
        if self._queued_stream - LAG > 0:
            with annotate("bench.wait", self.annotations):
                self.watch.wait_stream(self._queued_stream - LAG)
        with annotate("bench.step", self.annotations):
            busy = self.eng.step()
        self._after_step()
        return busy

    def _after_step(self) -> None:
        eng = self.eng
        stream_len = len(eng._stream)
        decoded = stream_len > self._queued_stream
        follows = stream_len - 1 if decoded else stream_len
        spans = eng.obs.tracer.spans
        for sp in spans[self._span_seen:]:
            if sp.name != "prefill_chunk":
                continue
            a = sp.attrs
            req = self.reqs[a["rid"]]
            live = min(self._chunk_width, req.prompt_len - a["t0"])
            self.chunks.append(Chunk(a["rid"], a["t0"], live,
                                     a["t0"] + live >= req.prompt_len, follows))
        self._span_seen = len(spans)
        # first tokens were sampled before this step's decode: queue them first
        for rid, r in eng._requests.items():
            if rid not in self._queued_first and r.first_tok is not None:
                self._queued_first.add(rid)
                self.watch.put(("f", rid), r.first_tok)
        for s in range(self._queued_stream, stream_len):
            self.watch.put(("s", s), eng._stream[s])
        self._queued_stream = stream_len
        reg = eng.obs.registry
        self.gauges.append((time.perf_counter(),
                            reg.gauge("pool.capacity_tokens").value(),
                            reg.gauge("pool.live_tokens").value()))

    def replied(self, req: Req) -> float | None:
        """When the request's last token was ready, once the engine has
        finished it."""
        r = self.eng._requests[req.rid]
        if not r.done:
            return None
        if r.generated <= 1:
            return self.watch.stamp(("f", req.rid))
        return self.watch.stamp(("s", r.admit_step + r.generated - 2))

    def first_ready(self, req: Req) -> float | None:
        return self.watch.stamp(("f", req.rid))

    def settle(self) -> None:
        """Wait for every dispatched token, stop the watcher, and fill in each
        request's token stamps and each decode step's contexts."""
        self.watch.close()
        stamps = self.watch.stamps
        steps: dict[int, Step] = {}
        for req in self.reqs.values():
            r = self.eng._requests[req.rid]
            req.first = stamps.get(("f", req.rid))
            req.done = bool(r.done)
            if req.first is None:
                continue
            req.stamps = [req.first]
            for i in range(r.generated - 1):
                s = r.admit_step + i
                req.stamps.append(stamps[("s", s)])
                step = steps.setdefault(s, Step(s, stamps[("s", s)], []))
                step.ctxs.append(req.prompt_len + i + 1)
        self.steps = [steps[s] for s in sorted(steps)]

    def tokens(self, req: Req) -> list[int]:
        """The tokens the engine served to ``req`` (reads the device)."""
        import numpy as np

        r = self.eng._requests[req.rid]
        first = int(jax.device_get(r.first_tok))
        rows = self.eng._stream[r.admit_step:r.admit_step + r.generated - 1]
        rest = [int(t) for t in np.asarray(jax.device_get(rows))[:, r.slot]] if rows else []
        return [first, *rest]


def run_open(drv: Load, schedule, *, t0: float, t_open: float, t_close: float,
             hooks) -> None:
    """Open loop: each planned request is due at ``t0 + due``; arrivals go
    on through the drain, which lasts until every request due in the window
    has its first token (or ``DRAIN_S`` has passed)."""
    todo = list(schedule)
    k = 0
    window_due: list[Req] = []
    while True:
        now = time.perf_counter()
        hooks(now)
        while k < len(todo) and t0 + todo[k].due <= now:
            due = t0 + todo[k].due
            req = drv.submit(todo[k], due)
            if t_open <= due < t_close:
                window_due.append(req)
            k += 1
        if now >= t_close:
            if all(drv.first_ready(r) is not None for r in window_due):
                return
            if now >= t_close + DRAIN_S:
                return
        if not drv.step():
            nxt = t0 + todo[k].due if k < len(todo) else now + IDLE_POLL_S
            with annotate("bench.idle", drv.annotations):
                _sleep_until(min(nxt, now + IDLE_POLL_S))


def run_closed(drv: Load, schedule, *, clients: int, stagger_s: float, t0: float,
               t_open: float, t_close: float, hooks) -> None:
    """Closed loop: ``clients`` callers, each sending its next request when
    the reply to its last one is ready; client ``c`` sends its first at
    ``t0 + c * stagger_s / clients``.  Nothing is sent after the close;
    the drain lasts until every request sent in the window has its first
    token (or ``DRAIN_S`` has passed)."""
    todo = iter(schedule)
    starts = [t0 + c * stagger_s / clients for c in range(clients)]
    waiting: list[Req] = []
    window_due: list[Req] = []
    while True:
        now = time.perf_counter()
        hooks(now)
        while len(waiting) < clients and starts[len(waiting)] <= now:
            waiting.append(drv.submit(next(todo), starts[len(waiting)], client=len(waiting)))
        if now >= t_close:
            if all(drv.first_ready(r) is not None for r in window_due):
                return
            if now >= t_close + DRAIN_S:
                return
        else:
            still = []
            for req in waiting:
                t = drv.replied(req)
                if t is None:
                    still.append(req)
                    continue
                try:
                    nxt = drv.submit(next(todo), t, client=req.client)
                except StopIteration:
                    raise RuntimeError("the closed loop ran out of planned requests") from None
                still.append(nxt)
                if t_open <= t < t_close:
                    window_due.append(nxt)
            waiting = still
        if not drv.step():
            with annotate("bench.idle", drv.annotations):
                _sleep_until(now + IDLE_POLL_S)


def _sleep_until(t: float) -> None:
    dt = t - time.perf_counter()
    if dt > 0:
        time.sleep(dt)
