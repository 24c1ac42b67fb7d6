"""One run of one cell: set-up, the measured window, the check.

The window drives the public surface of ``repro.api.open_store(spec)``:
``get_batch(keys, xp=jnp)`` and ``update_batch(keys, values)`` (and
``insert_batch`` where a mix has inserts), one closed-loop client issuing
fixed rounds in the mix's order.  Every answer of the window is kept and,
once the window has closed and the store is freed, compared with the plain
reference replaying the same operations.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import sys
import tempfile
import time

import numpy as np

from lib import gen, reference, trace as trace_lib

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
SPAN_WINDOW, SPAN_ROUND = "bench.window", "bench.round"


def span_name(op: str) -> str:
    return f"bench.{op}"


@dataclasses.dataclass
class Call:
    op: str
    lanes: int
    issued: float  # perf_counter when the round was issued
    start: float  # perf_counter when this call began
    end: float  # perf_counter when it returned


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read about one run."""

    cell: str
    seed: int
    config: dict
    traffic: dict
    peaks: dict | None
    setup_s: float
    build_s: float
    window_start: float
    window_end: float
    calls: list[Call]
    wrong: dict[str, int]  # op -> lanes answered wrongly
    compared: dict[str, int]  # op -> lanes compared
    compiles: list[tuple[float, float]]  # (start, seconds) of each compile
    meter: dict[str, int]  # window deltas of the store's meter totals
    trace: trace_lib.Trace | None = None
    memory_peak_bytes: int = 0

    @property
    def window_s(self) -> float:
        return self.window_end - self.window_start

    def ops(self, op: str | None = None) -> int:
        return sum(c.lanes for c in self.calls if op in (None, c.op))

    def latencies(self, op: str) -> tuple[np.ndarray, np.ndarray]:
        """(seconds from round issue to the return of the call that
        answered it, lanes that waited that long) per call of ``op``."""
        cs = [c for c in self.calls if c.op == op]
        return (np.asarray([c.end - c.issued for c in cs]),
                np.asarray([c.lanes for c in cs]))

    def ops_correct(self) -> int:
        return self.ops() - sum(self.wrong.values())

    def compiles_in_window(self) -> int:
        return sum(1 for s, _ in self.compiles
                   if self.window_start <= s <= self.window_end)

    @functools.cached_property
    def busy(self) -> list:
        """Per device, the disjoint intervals of the trace's operations."""
        return self.trace.busy() if self.trace is not None else []

    def traced_window(self) -> tuple[float, float] | None:
        """The window on the trace's clock, if the run was traced."""
        spans = self.trace.span(SPAN_WINDOW) if self.trace else []
        return spans[0] if spans else None


class CompileCounter:
    """Start time and length of every backend compile while installed."""

    def __init__(self):
        self.events: list[tuple[float, float]] = []

    def __call__(self, event: str, seconds: float, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.events.append((time.perf_counter() - seconds, seconds))

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _issue(store, op: str, keys, values):
    import jax.numpy as jnp
    if op == "get":
        return store.get_batch(keys, xp=jnp)
    if op == "update":
        return store.update_batch(keys, values)
    if op == "insert":
        return store.insert_batch(keys, values)
    raise ValueError(f"unknown op {op!r}")


def program_store(spec: dict, keys, values):
    """The system under test: ``repro.api.open_store`` of the spec."""
    from repro.api import StoreSpec, open_store
    return open_store(StoreSpec.from_json_dict(spec), keys, values)


def _meter(store) -> dict[str, int]:
    m = store.meter_totals()
    return {"cache_hits": int(m.cache_hits),
            "cache_neg_hits": int(m.cache_neg_hits)}


def run_cell(cell: str, config: dict, traffic: dict, seed: int,
             seconds: float, trace: bool, *, t_process: float,
             peaks: dict | None = None, open_store=None) -> Run:
    """Build the cell's store from ``seed``, warm it, measure ``seconds``
    and check every answer.  ``open_store(spec_dict, keys, values)`` builds
    the store; by default the program's ``repro.api.open_store``."""
    import jax
    from jax.profiler import TraceAnnotation

    if open_store is None:
        open_store = program_store
    keys = gen.make_keys(int(config["pairs"]), seed)
    values = gen.values_of(keys)
    mix = gen.Traffic(traffic, keys, seed)

    t0 = time.perf_counter()
    store = open_store(config["store_spec"], keys, values)
    build_s = time.perf_counter() - t0
    log(f"build_s={build_s!r}")

    # cache fill through the host path, Gets only, until its hit share
    # levels off: the data stays as loaded
    fill = []
    settle = traffic.get("cache_fill_settle")
    fill_gets = max(1, keys.shape[0] // gen.FILL_ROUND_DIV)
    while settle is not None and len(fill) < gen.FILL_MAX_ROUNDS \
            and not gen.fill_settled(fill, settle):
        q = mix.get_round(gen.FILL_STREAM, len(fill), fill_gets)
        fill.append(store.get_batch(q).cache_hits / q.shape[0])
    if fill:
        log(f"cache_fill_hit_share first={fill[0]:.4f} "
            f"last={fill[-1]:.4f} rounds={len(fill)} gets={fill_gets} "
            f"settled={gen.fill_settled(fill, settle)}")

    # warm-up of every shape the window issues: updates write the loaded
    # value back, so the data the window starts from is the loaded data
    for r in range(int(traffic.get("warmup_rounds", 2))):
        for op, q, v in mix.round(gen.WARM_STREAM, r, writes=False):
            _issue(store, op, q, v)

    calls: list[Call] = []
    answers: list[tuple[str, np.ndarray, np.ndarray | None, object]] = []
    trace_dir = tempfile.TemporaryDirectory(prefix="bench-trace-") \
        if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir.name, profiler_options=opts)
    gc.collect()
    m0 = _meter(store)
    with CompileCounter() as compiles:
        w0 = time.perf_counter()
        setup_s = w0 - t_process
        deadline = w0 + seconds
        rnd = 0
        with TraceAnnotation(SPAN_WINDOW):
            while rnd == 0 or time.perf_counter() < deadline:
                ops = mix.round(gen.WINDOW_STREAM, rnd)
                with TraceAnnotation(SPAN_ROUND):
                    issued = time.perf_counter()
                    for op, q, v in ops:
                        start = time.perf_counter()
                        with TraceAnnotation(span_name(op)):
                            res = _issue(store, op, q, v)
                        calls.append(Call(op, q.shape[0], issued,
                                          start, time.perf_counter()))
                        answers.append((op, q, v, res))
                rnd += 1
        w1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    m1 = _meter(store)
    memory = jax.devices()[0].memory_stats() or {}
    log(f"window rounds={rnd} seconds={w1 - w0!r} "
        f"compiles={sum(1 for s, _ in compiles.events if w0 <= s <= w1)}")
    del store
    gc.collect()

    ref = reference.Reference(keys, values)
    del keys, values
    wrong = {op: 0 for op, _ in mix.counts}
    compared = dict.fromkeys(wrong, 0)
    for op, q, v, res in answers:
        wrong[op] += reference.compare(ref, op, q, res.values, res.found,
                                       ack_values=v)
        compared[op] += q.shape[0]

    tr = None
    if trace:
        tr = trace_lib.load(trace_dir.name)
        trace_dir.cleanup()
    return Run(cell=cell, seed=seed, config=config, traffic=traffic,
               peaks=peaks, setup_s=setup_s, build_s=build_s,
               window_start=w0, window_end=w1, calls=calls, wrong=wrong,
               compared=compared, compiles=compiles.events,
               meter={k: m1[k] - m0[k] for k in m0},
               trace=tr,
               memory_peak_bytes=int(memory.get("peak_bytes_in_use", 0)))
