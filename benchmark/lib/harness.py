"""What every runner needs around the system under test: the compile
listener, the profiler slice, the device line, and the context the
per-layer readers read from."""

from __future__ import annotations

import shutil
import threading
import time
from pathlib import Path

from benchmark.lib import reduce_trace

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_T0 = time.perf_counter()


class CompileLog:
    """Every XLA compile request of the process, with the time it came
    at and how long it took (a persistent-cache hit is a short one). From
    `jax.monitoring`; the sum before the window is `compile_s`, and the
    count inside it must be 0."""

    def __init__(self):
        import jax.monitoring

        self.events: list[tuple[float, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.events.append((time.perf_counter(), float(duration)))

    def seconds_before(self, t: float) -> float:
        return sum(d for at, d in self.events if at < t)

    def count_between(self, t0: float, t1: float) -> int:
        return sum(1 for at, _ in self.events if t0 <= at < t1)


def bench_marker(x):
    """The device-side mark of a slice's ends (see `ProfilerSlice`); the
    trace prints its program as `jit_bench_marker`."""
    return x + 1


class ProfilerSlice:
    """`seconds` of jax's profiler in the middle of the window, run by a
    helper thread so that the loop under measurement never waits for the
    profiler to start or to write its trace (stopping took seconds and
    stalled the server when the loop did it itself). The thread writes
    the two annotations the reduction needs: `bench.sync`, at a known
    time of the program tracer's clock, and `bench.profile_window` around
    the slice. Python-level tracing is off: it slows the host it is meant
    to observe. With `host=False` nothing of the host is traced, the
    annotations included: tracing the runtime's transfer threads (2.5 M
    events in 1.5 s) slowed `fit`'s input path until one step was
    dispatched in the slice and none ran (my chip run, PR 22). The slice
    is then marked on the device itself: a one-operation program,
    `bench_marker`, compiled here during set-up, runs at either end of
    it, and the first one's start stands for the `bench.sync` time (it
    waits for whatever step is running, so the join is good to a step's
    length, not to a microsecond)."""

    def __init__(self, out_dir: Path, seconds: float, *, host: bool = True):
        self.dir, self.seconds, self.host = Path(out_dir), seconds, host
        self._mark = None
        if not host:
            import jax
            import jax.numpy as jnp

            x = jnp.zeros((), jnp.float32)
            marker = jax.jit(bench_marker)
            self._mark = lambda: marker(x).block_until_ready()
            self._mark()
        self.sync_clock_s: float | None = None
        self._thread: threading.Thread | None = None

    @property
    def started(self) -> bool:
        return self._thread is not None

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1 if self.host else 0   # TraceAnnotations
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(reduce_trace.SYNC):
                self.sync_clock_s = time.perf_counter()
            if self._mark is not None:
                self._mark()
            with jax.profiler.TraceAnnotation(reduce_trace.WINDOW):
                time.sleep(self.seconds)
            if self._mark is not None:
                self._mark()
        finally:
            jax.profiler.stop_trace()

    def load(self) -> dict | None:
        """Wait for the slice to be written, and read it. None when the
        slice never started or left no trace."""
        if self._thread is None:
            return None
        self._thread.join()
        path = reduce_trace.find_xplane(self.dir)
        return None if path is None else reduce_trace.load_xplane(path)


def note(msg: str) -> None:
    """A line on standard error with the process's age and resident
    memory: where set-up time and host memory go, for whoever reads a
    run by hand. The result line is on standard output alone."""
    import resource
    import sys

    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
    print(f"[bench {time.perf_counter() - _T0:7.2f}s peak-rss {rss:5.1f} GB] "
          f"{msg}", file=sys.stderr, flush=True)


def annotate(name: str):
    """A host span in the profiler's own trace (a no-op when no trace runs)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def keep_every_compile() -> None:
    """One fixed cache directory (the program's own placement: the
    environment's, or <checkout>/.jax_cache), and every compile kept,
    however short: the serve path makes hundreds under a second, which
    jax's default thresholds would compile again in every process."""
    import jax

    from idc_models_tpu import runtime

    runtime.setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def require_tpu(chips: int, *, rehearse: bool) -> dict:
    """The device line, as jax reports it. Without `rehearse`, anything
    but `chips` TPU chips ends the run: a measurement path never falls
    back to the CPU."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if rehearse:
        if len(devs) < chips:
            raise SystemExit(f"rehearsal needs {chips} devices, jax has "
                             f"{len(devs)}")
        return info | {"count": chips}
    if info["platform"] != "tpu" or len(devs) < chips:
        raise SystemExit(
            f"this cell needs {chips} TPU chip(s); jax found {len(devs)} "
            f"device(s) of platform {info['platform']!r}")
    return info | {"count": chips}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes held on the fullest of the chips used: the allocator's
    peak of bytes in use (arrays: weights, caches, batches) plus its peak
    of bytes reserved, which is where the TPU runtime keeps a loaded
    program's temporaries and which `peak_bytes_in_use` leaves out (a
    train step that needs 12 GB of them shows 2 GB in use). 0 where the
    backend reports nothing, as the CPU does."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks, default=0)


def program_spans_on_profiler_clock(records, mono_t0: float,
                                    offset_ns: float):
    """The program tracer's records as (name, start_ns, end_ns) on the
    profiler's clock."""
    out = []
    for r in records:
        start = (mono_t0 + r["t_ms"] / 1e3) * 1e9 + offset_ns
        out.append((r["name"], start, start + r["dur_ms"] * 1e6))
    return out


class Context:
    """What a per-layer reader may read: the cell's three data files, the
    table of peaks, flat `counters` (from the compile listener, the
    program's `summary()`, the runner's own bookkeeping and the device's
    memory statistics), the program's spans inside the measured window,
    and the reduced device trace of the profiled slice."""

    def __init__(self, *, cell, config, traffic, peaks, counters,
                 span_records, trace, window, metric_files):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.peaks = peaks
        self.counters = counters
        self.span_records = span_records      # [{"name","t_ms","dur_ms"}]
        self.trace = trace                    # reduce_trace's dict | None
        self.window = window                  # (t0_ns, t1_ns) | None
        self._metric_files = metric_files     # name -> parsed json
        self._memo: dict[str, float | None] = {}

    def resolve(self, ref):
        """A reader argument: `"@counters.x"`, `"@config.model"` and
        `"@traffic.a.b"` are looked up, `{"metric": name, "scale": s}`
        is another per-layer metric's value, anything else is itself."""
        if isinstance(ref, dict) and "metric" in ref:
            v = self.metric(ref["metric"])
            return None if v is None else v * ref.get("scale", 1.0)
        if not (isinstance(ref, str) and ref.startswith("@")):
            return ref
        root, _, path = ref[1:].partition(".")
        if root == "counters":
            return self.counters.get(path)
        node = {"config": self.config, "traffic": self.traffic,
                "peaks": self.peaks, "cell": self.cell}[root]
        for part in path.split(".") if path else []:
            node = node[part]
        return node

    def metric(self, name: str):
        """The value of the per-layer metric `name`, or None where its
        reader finds nothing to read."""
        if name not in self._memo:
            import importlib

            spec = self._metric_files[name]
            reader = importlib.import_module(
                f"benchmark.readers.{spec['reader']}")
            self._memo[name] = reader.read(self, **spec.get("args", {}))
        return self._memo[name]
