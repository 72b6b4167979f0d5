"""What every run shares: the device check, the compile cache, the
compile clock, host spans, set-up phases and the measured window."""

from __future__ import annotations

import contextlib
import os
import sys
import threading
import time

from harness import tracereduce

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
REQUIRED_PLATFORM = "tpu"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def check_devices(chips: int):
    """The JAX devices, or :class:`NoChip`.  Never falls back to the CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != REQUIRED_PLATFORM:
        raise NoChip(
            f"no {REQUIRED_PLATFORM} device: JAX found "
            f"{devices[0].platform!r}"
        )
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    return devices


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache where the program keeps it
    (``repro.launch.compile_cache``: ``$JAX_COMPILATION_CACHE_DIR`` when
    set, else the fixed ``<checkout>/.jax_cache``), cache every program
    however short its compile, and never evict."""
    import jax
    from repro.launch import compile_cache

    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


class CompileClock:
    """Counts XLA compiles (persistent-cache reads included) from JAX's
    ``backend_compile_duration`` events, with the time of each."""

    def __init__(self):
        self._lock = threading.Lock()
        self.events: list[tuple[float, float]] = []  # (end time, seconds)

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            with self._lock:
                self.events.append((time.perf_counter(), duration))

    def count(self, t0: float = float("-inf"), t1: float = float("inf")):
        with self._lock:
            return sum(1 for t, _ in self.events if t0 <= t <= t1)

    def seconds(self, t0: float = float("-inf"), t1: float = float("inf")):
        with self._lock:
            return sum(d for t, d in self.events if t0 <= t <= t1)


class Run:
    """One run of one cell: its inputs, scratch directory, clocks and
    window.  The mix's driver (``harness.<driver>``) fills in the
    outcome."""

    def __init__(self, *, workload: dict, config: dict, mix: dict,
                 seed: int, seconds: float, trace: bool, tmp: str,
                 t_start: float, log=None):
        self.workload = workload
        self.config = config
        self.mix = mix
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.tmp = tmp
        self.t_start = t_start
        self.log = log or (lambda k, v: print(f"{k}={v}", flush=True))
        self.compiles = CompileClock()
        self.setup_phases: dict[str, float] = {}
        self.window: "tuple[float, float] | None" = None
        self.trace_dir = os.path.join(tmp, "trace")
        self.reduced: "tracereduce.Reduced | None" = None
        self.memory_peak_bytes = 0
        self._window_span = None
        self._tracing = False
        # filled by the driver
        self.e2e: dict[str, float] = {}
        self.checks: dict[str, tuple[float, float]] = {}
        self.attempted = 0
        self.failed = 0
        self.layer: dict = {}  # what the per-layer readers read

    # -- set-up --------------------------------------------------------

    @contextlib.contextmanager
    def setup_phase(self, name: str):
        t0 = time.perf_counter()
        with self.span(f"bench.{name}"):
            yield
        self.setup_phases[name] = (
            self.setup_phases.get(name, 0.0) + time.perf_counter() - t0
        )

    def span(self, name: str):
        """A host span on the profiler's clock (a no-op when not tracing)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    # -- the measured window ------------------------------------------

    def start_trace(self) -> None:
        """Start the profiler (with ``--trace 1``); :meth:`window_begin`
        does so itself where starting it may block the window."""
        if self.trace and not self._tracing:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._tracing = True

    def window_begin(self) -> float:
        """Close set-up and open the window; returns its start."""
        self.start_trace()
        if self.trace:
            import jax

            self._window_span = jax.profiler.TraceAnnotation(
                tracereduce.WINDOW_SPAN
            )
            self._window_span.__enter__()
        t0 = time.perf_counter()
        self.window = (t0, t0)
        return t0

    def close_window_span(self) -> None:
        """End the window's host span (the trace runs on until
        :meth:`window_end`)."""
        if self._window_span is not None:
            self._window_span.__exit__(None, None, None)
            self._window_span = None

    def window_end(self, t1: "float | None" = None) -> None:
        t1 = time.perf_counter() if t1 is None else t1
        self.window = (self.window[0], t1)
        self.close_window_span()
        if self._tracing:
            import jax

            jax.profiler.stop_trace()
            self._tracing = False

    @property
    def setup_s(self) -> float:
        return self.window[0] - self.t_start

    @property
    def window_compiles(self) -> int:
        return self.compiles.count(*self.window)

    def read_memory(self, devices) -> None:
        peaks = [
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devices
        ]
        self.memory_peak_bytes = int(max(peaks))

    def reduce_trace(self) -> None:
        if self.trace:
            self.reduced = tracereduce.reduce(
                tracereduce.find_xplane(self.trace_dir)
            )


def stderr(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
