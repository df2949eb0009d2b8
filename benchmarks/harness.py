"""What every driver needs from the harness: the run's arguments and files, the
clock that set-up is counted on, the lines printed before the result, the trace."""

from __future__ import annotations

import contextlib
import os
import shutil
import sys
import time
from dataclasses import dataclass, field

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")  # where a traced window's profile is written


@dataclass
class Run:
    """One run of one cell, as a driver sees it."""
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    started: float  # time.perf_counter() when the process began its work
    devices: list = field(default_factory=list)
    tracer: "Tracer | None" = None  # set for a --trace 1 run

    marks: list = field(default_factory=list)  # (what, seconds since the last mark)
    _marked: float = 0.0

    def log(self, text: str) -> None:
        print(text, flush=True)

    def mark(self, what: str) -> None:
        """One more part of set-up done; ``log_setup`` prints where it went."""
        now = time.perf_counter()
        self.marks.append((what, now - (self._marked or self.started)))
        self._marked = now

    def log_setup(self) -> None:
        self.log("setup: " + ", ".join(f"{what} {s:.2f} s" for what, s in self.marks))

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span on the trace's clock while the profiler is on; nothing else."""
        tracer = self.tracer
        if tracer is None or not tracer.active:
            yield
            return
        start = time.perf_counter()
        try:
            yield
        finally:
            tracer.spans.append((name, start - tracer.zero, time.perf_counter() - tracer.zero))


@dataclass
class Outcome:
    """What a driver hands back once its window has closed."""
    attempted: int
    failed: int  # refused or failed: counted, and never read as a fast answer
    lost: int  # answers that never came, or rows missing: these make a run not correct
    setup_s: float
    end_to_end: dict  # metric name -> value, without setup_s
    observed: dict  # counters and readings for the per-layer readers
    evidence: dict  # what the timed path was given and what it answered, for the
    # comparer that the configuration's ``correct`` block names; it says which keys
    memory_peak_bytes: int
    release: object  # callable: drops the program's state before the reference runs


def memory_peak_bytes(devices) -> int:
    """Peak bytes on the fullest device, 0 where the backend does not say: the
    peak of the buffers in use plus the peak reserved for the programs' scratch.
    On the v5e runtime here a program's temporaries (4.27 GB for InceptionV3 at
    batch 1,024) are carved out of ``bytes_reserved`` and never show in
    ``peak_bytes_in_use``; ``largest_free_block_bytes`` falls by both."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", 0) + stats.get("peak_bytes_reserved", 0))
    return int(max(peaks)) if peaks else 0


def image_rows(seed: int, rows: int, shape) -> np.ndarray:
    """``rows`` uint8 images, all different, from the seed. SFC64's raw 64-bit words
    fill memory several times faster than ``integers(dtype=uint8)``."""
    n = rows * int(np.prod(shape))
    bits = np.random.Generator(np.random.SFC64(int(seed)))
    raw = bits.integers(0, 2**63, size=(n + 7) // 8, dtype=np.int64)
    return raw.view(np.uint8)[:n].reshape((rows,) + tuple(shape))


class Tracer:
    """The profiler round a traced window, with the driver's own spans beside it.

    The host tracer stays off: with it on, every host-to-device copy of a uint8
    image batch writes some six million ``Transpose`` events (217 MB of trace and
    2.4 s for one step of 1,024 rows, my chip run, PR 25), which both slows the
    window and overruns the host's memory. So the profiler's ``TraceAnnotation``
    spans are not there to read, and the spans are kept here instead, on the
    trace's clock: its zero is the moment ``start_trace`` was called (the two agree
    to 0.05 ms in the same run)."""

    def __init__(self):
        self.log_dir = TRACE_DIR
        self.spans: list = []  # (name, start_s, end_s) from the trace's zero
        self.window: tuple | None = None
        self.zero = 0.0
        self.active = False

    def start(self) -> None:
        import jax
        shutil.rmtree(self.log_dir, ignore_errors=True)
        os.makedirs(self.log_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = 0
        options.python_tracer_level = 0
        self.zero = time.perf_counter()
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        self._start = time.perf_counter() - self.zero
        self.active = True

    def stop(self) -> None:
        import jax
        self.window = (self._start, time.perf_counter() - self.zero)
        self.active = False
        jax.profiler.stop_trace()

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


def eprint(*args) -> None:
    print(*args, file=sys.stderr, flush=True)
