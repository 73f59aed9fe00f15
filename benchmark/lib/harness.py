"""What every driver shares: file lookup by name, the compile counter, host
spans, the profiler window, the device block of the result line."""
import contextlib
import importlib.util
import json
import os
import shutil
import time

PACKAGE_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path):
    name = "_bench_" + "".join(c if c.isalnum() else "_" for c in
                               os.path.relpath(path, PACKAGE_DIR))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Files:
    """Finds a traffic mix, a metric reader, a driver, an adapter or a
    reference by its name: first under the benchmark's ``paths`` beside the
    BENCHMARK.json in use, then in this package."""

    def __init__(self, root, paths):
        self.root = root
        self.dirs = [os.path.join(root, p) for p in paths]
        if PACKAGE_DIR not in self.dirs:
            self.dirs.append(PACKAGE_DIR)

    def find(self, kind, filename):
        for d in self.dirs:
            path = os.path.join(d, kind, filename)
            if os.path.exists(path):
                return path
        raise FileNotFoundError("no %s/%s under %s"
                                % (kind, filename, self.dirs))

    def module(self, kind, name):
        return load_module(self.find(kind, name + ".py"))

    def json(self, kind, name):
        return load_json(self.find(kind, name + ".json"))


class Compiles:
    """Programs this process obtained an executable for, the seconds jax's
    backend spent compiling them, and how many came out of the persistent
    cache (copied from chip_smoke.Compiles)."""

    def __init__(self):
        import jax.monitoring
        self.programs = 0
        self.cache_hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += seconds

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Spans:
    """Host seconds by span name, each span also written into the profiler's
    trace (a TraceAnnotation costs nothing while no trace is being taken)."""

    def __init__(self):
        self.seconds = {}
        self.counts = {}

    @contextlib.contextmanager
    def span(self, name):
        import jax.profiler
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.seconds[name] = self.seconds.get(name, 0.0) \
            + time.perf_counter() - t0
        self.counts[name] = self.counts.get(name, 0) + 1

    def reset(self):
        self.seconds.clear()
        self.counts.clear()


def trace_dir(root):
    """A fixed directory inside the checkout, emptied before each trace."""
    d = os.path.join(root, ".cache", "bench_trace")
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d)
    return d


@contextlib.contextmanager
def profiler_window(directory):
    """Device and host-annotation tracing without the Python tracer (its
    events would outnumber the device's a hundred to one)."""
    import jax.profiler
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def device_block(devices):
    """The device as jax reports it.  ``memory_peak_bytes`` is the peak on the
    fullest chip: the allocator's ``peak_bytes_in_use`` (arrays) plus its
    ``peak_bytes_reserved``, which is where this runtime keeps the
    temporaries a running program plans (7.7 GB of the gpt2s training step's
    8.7 GB; ``peak_bytes_in_use`` alone read 1.96 GB there)."""
    block = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices)}
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", 0)
                     + stats.get("peak_bytes_reserved", 0))
    block["memory_peak_bytes"] = int(max(peaks))
    return block
