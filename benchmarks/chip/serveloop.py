"""The measured window: seeded traffic served on the wall clock.

The window drives the program's own scheduler (``BatchScheduler.step``
over its ``ServeEngine``, greedy admission, no latency probe) and keeps
its own schedule and stamps on ``time.perf_counter()``.  The
scheduler's clock is virtual and nothing here reads it, nor a
``Request``'s timestamps: a request is pushed into the queue as ready
when it is due, and its first and later tokens are stamped from the
``emitted`` counts after each ``step()``, when the step has handed its
tokens to the host.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import math
import time

import numpy as np


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or reading the
    persistent cache), keyed by the jitted function's name, and the
    number of traces."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.secs = collections.Counter()
        self.traces = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event in self.EVENTS:
            self.secs[str(kw.get("fun_name", "?"))] += duration
        if event == self.EVENTS[0]:
            self.traces += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Spans:
    """Host spans of the harness, kept in memory: (name, start, end) on
    ``perf_counter``.  With ``annotate`` each also goes into the
    profiler's trace as a ``TraceAnnotation`` of the same name."""

    NAMES = frozenset({"sched.step", "engine.start_cohort",
                       "engine.step_cohort", "harness.stamp",
                       "harness.submit", "harness.wait"})

    def __init__(self, annotate: bool = False):
        self.rows: list[tuple[str, float, float]] = []
        self.annotate = annotate

    @contextlib.contextmanager
    def span(self, name: str):
        ctx = contextlib.nullcontext()
        if self.annotate:
            import jax
            ctx = jax.profiler.TraceAnnotation(name)
        t0 = time.perf_counter()
        with ctx:
            yield
        self.rows.append((name, t0, time.perf_counter()))


@dataclasses.dataclass(frozen=True)
class Call:
    kind: str          # "prefill" or "decode"
    batch: int
    context: int       # prefill: prompt length; decode: cache positions
    t0: float
    t1: float


class TimedEngine:
    """The engine as the scheduler is handed it: every cohort call runs
    inside a span and is logged with its shape.  Nothing else changes."""

    def __init__(self, engine, spans: Spans):
        self.engine = engine
        self.spans = spans
        self.calls: list[Call] = []
        self._position = {}      # id(cohort state) -> cache positions

    def start_cohort(self, prompts, max_new=None, seed=0):
        t0 = time.perf_counter()
        with self.spans.span("engine.start_cohort"):
            state, tokens, wall = self.engine.start_cohort(prompts, max_new,
                                                           seed)
        b, s = prompts.shape
        self.calls.append(Call("prefill", b, s, t0, time.perf_counter()))
        self._position[id(state)] = s
        return state, tokens, wall

    def step_cohort(self, state, tokens):
        t0 = time.perf_counter()
        with self.spans.span("engine.step_cohort"):
            out = self.engine.step_cohort(state, tokens)
        self._position[id(state)] += 1
        self.calls.append(Call("decode", state.batch,
                               self._position[id(state)], t0,
                               time.perf_counter()))
        return out


@dataclasses.dataclass
class Stamp:
    rid: int
    prompt_len: int
    max_new: int
    due: float
    first: float | None = None
    last: float | None = None
    emitted: int = 0
    row: int | None = None   # its row in its cohort, and the cohort's rows
    rows: int = 0

    @property
    def finished(self) -> bool:
        return self.emitted >= self.max_new

    @property
    def back_half(self) -> bool:
        """In the back half of a cohort of two rows or more."""
        return self.row is not None and self.row >= (self.rows + 1) // 2


@dataclasses.dataclass
class Window:
    t0: float
    t_end: float
    t_drained: float
    stamps: list            # Stamp of every request submitted in the window
    requests: dict          # rid -> the program's Request (its tokens)
    token_log: list         # (time, context) of every decode-made token
    lateness_s: list        # open loop: how late each request was pushed
    steps: int


def serve_window(engine, traffic, seconds: float, spans: Spans, *,
                 drain_s: float = 60.0, on_tick=None) -> Window:
    """Serve ``traffic`` for ``seconds``, then stop submitting and keep
    serving until every request submitted in the window has finished (at
    most ``drain_s`` more).  ``on_tick(elapsed)`` is called between
    scheduler steps."""
    from repro.serving.admission import AdmissionController
    from repro.serving.queue import Request, RequestQueue
    from repro.serving.scheduler import BatchScheduler

    queue = RequestQueue()
    sched = BatchScheduler(
        queue=queue,
        admission=AdmissionController(capacity=traffic.capacity,
                                      policy="greedy"),
        engine=engine, probe=None, eos_id=None, seed=0)
    live: dict = {}
    stamps, requests, token_log, lateness = [], {}, [], []
    steps = 0
    next_i = 0

    def submit(due: float):
        nonlocal next_i
        prompt_len, max_new = traffic.lengths(next_i)
        with spans.span("harness.submit"):
            req = Request(rid=next_i, arrival_s=0.0,
                          prompt=traffic.prompt(next_i), max_new=max_new)
            queue.push(req)
        st = Stamp(next_i, prompt_len, max_new, due)
        live[next_i] = (req, st)
        stamps.append(st)
        requests[next_i] = req
        next_i += 1

    t0 = time.perf_counter()
    t_end = t0 + seconds
    if traffic.loop == "closed":
        for _ in range(traffic.clients):
            submit(t0)
    while True:
        now = time.perf_counter()
        if on_tick is not None:
            on_tick(now - t0)
        if traffic.loop == "open":
            while next_i < len(traffic) and \
                    t0 + traffic.arrivals[next_i] <= now:
                due = t0 + traffic.arrivals[next_i]
                lateness.append(now - due)
                submit(due)
        if sched.cohorts or len(queue):
            with spans.span("sched.step"):
                sched.step()
            steps += 1
            t = time.perf_counter()
            with spans.span("harness.stamp"):
                for cohort in sched.cohorts:
                    for row, req in enumerate(cohort.requests):
                        st = live[req.rid][1] if req.rid in live else None
                        if st is not None and st.row is None:
                            st.row, st.rows = row, len(cohort.requests)
                done = []
                for rid, (req, st) in live.items():
                    if req.emitted > st.emitted:
                        if st.first is None:
                            st.first = t
                        st.last = t
                        token_log.extend((t, st.prompt_len + k) for k in
                                         range(max(1, st.emitted),
                                               req.emitted))
                        st.emitted = req.emitted
                    if req.done:
                        done.append(rid)
                for rid in done:
                    del live[rid]
            if traffic.loop == "closed" and t < t_end:
                for _ in done:
                    submit(t)
            if t >= t_end + drain_s:
                break
            continue
        # nothing queued or in flight
        if now >= t_end:
            break
        wake = t_end
        if traffic.loop == "open" and next_i < len(traffic):
            wake = min(wake, t0 + traffic.arrivals[next_i])
        with spans.span("harness.wait"):
            time.sleep(max(0.0, wake - time.perf_counter()))
    return Window(t0=t0, t_end=t_end, t_drained=time.perf_counter(),
                  stamps=stamps, requests=requests,
                  token_log=token_log,
                  lateness_s=lateness, steps=steps)


def percentile(values, q: float) -> float:
    """The ``q``-th percentile, linear between closest ranks (numpy's
    default); nan for no values."""
    vs = sorted(values)
    if not vs:
        return float("nan")
    pos = (len(vs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(vs) - 1)
    return vs[lo] + (vs[hi] - vs[lo]) * (pos - lo)


def tpot_s(st: Stamp) -> float | None:
    """Seconds per output token after the first: (last - first) over the
    tokens after the first; None below two tokens."""
    if st.first is None or st.emitted < 2:
        return None
    return (st.last - st.first) / (st.emitted - 1)


def latencies(w: Window) -> dict:
    """TTFT of every request submitted in the window that got a first
    token (from its due time), TPOT of every one that finished, and the
    requests that never finished."""
    ttft = [st.first - st.due for st in w.stamps if st.first is not None]
    tpot = [v for v in (tpot_s(st) for st in w.stamps if st.finished)
            if v is not None]
    unfinished = [st.rid for st in w.stamps if not st.finished]
    return {"ttft_s": ttft, "tpot_s": tpot, "unfinished": unfinished}


def tokens_per_s(w: Window) -> float:
    """All output tokens of the requests submitted in the window over the
    time from the window's opening until the last of them finished: all
    the work and all the time it took.  (Tokens stamped inside the window
    over its length would count a closed loop's rounds whole or not at
    all.)"""
    return sum(st.emitted for st in w.stamps) / (w.t_drained - w.t0)


def check_sample(w: Window, seed: int, k: int) -> list:
    """``k`` finished requests, drawn from the seed: the one with the
    most served tokens always among them (lowest id on ties), and, where
    a cohort had two rows or more, one from the back half of its cohort,
    so that rows computed from the front half's inputs cannot all go
    unread."""
    done = sorted((st for st in w.stamps if st.finished),
                  key=lambda st: (-st.emitted, st.rid))
    if not done:
        return []
    rng = np.random.default_rng([int(seed), 2])
    rest = done[1:]
    pick = []
    back = [i for i, st in enumerate(rest) if st.back_half]
    if back and k > 1 and not done[0].back_half:
        pick.append(back[rng.integers(len(back))])
    others = [i for i in range(len(rest)) if i not in pick]
    pick += rng.choice(others, size=min(k - 1 - len(pick), len(others)),
                       replace=False).tolist()
    return [done[0].rid] + [rest[i].rid for i in sorted(pick)]
