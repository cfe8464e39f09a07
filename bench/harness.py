"""The benchmark's shared machinery: finding a cell's files by name, the
device check, host spans around the program's callables, JAX's compile
events, the profiler trace of a steady part of the window, and the one
JSON result line.

Everything that belongs to one configuration, traffic mix, entry path or
per-layer metric lives in a file of its own (`configs/`, `traffic/`,
`paths/`, `metrics/`, `flops/`), found by the name `BENCHMARK.json` gives.
"""
from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

# JAX's compile events in JAX 0.9.0.  The backend-compile duration wraps
# `compile_or_get_cached`, so a persistent-cache load is already inside it
# and the cache's own retrieval time is not added again.
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class BenchError(RuntimeError):
    """A run that must end without a result line."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a benchmark file by path (names may hold dots)."""
    if not path.is_file():
        raise BenchError(f"missing benchmark file {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_files(workload: str, manifest: dict | None = None) -> dict:
    """A cell's entry, configuration, traffic mix and the per-layer metrics
    it reports, all found by name."""
    manifest = manifest or load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = load_json(ROOT / cfg_entry["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    e2e = [m for m in manifest["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in manifest["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return dict(cell=cell, config=config, traffic=traffic, end_to_end=e2e,
                per_layer=per_layer)


def resolve(dotted: str):
    """(owner, attribute) of a dotted path `pkg.module[.Class].attr`, or
    None when any part is missing."""
    parts = dotted.split(".")
    for cut in range(len(parts) - 1, 0, -1):
        try:
            obj = importlib.import_module(".".join(parts[:cut]))
        except ImportError:
            continue
        for p in parts[cut:-1]:
            obj = getattr(obj, p, None)
            if obj is None:
                return None
        return (obj, parts[-1]) if hasattr(obj, parts[-1]) else None
    return None


class Run:
    """One run of one cell: its arguments, files, host spans and compile
    events, and the measured window."""

    def __init__(self, args, files: dict, t_start: float):
        self.t_start = t_start
        self.seed, self.seconds, self.trace = args.seed, args.seconds, args.trace
        self.cell, self.config, self.traffic = (
            files["cell"], files["config"], files["traffic"])
        self.per_layer_specs = files["per_layer"]
        self.spans: list[tuple[str, float, float]] = []
        self.compiles: list[tuple[float, float]] = []   # (end time, seconds)
        self.counters: dict[str, float] = {}
        self.window: tuple[float, float] | None = None
        self.trace_result: dict | None = None
        self._trace_dir: Path | None = None
        self._trace_t: tuple[float, float] | None = None
        self.readers = {}
        self._installed: set[str] = set()
        self._patches: list[tuple[object, str, object]] = []
        self.control = False          # also read the control (bench/control.py)
        self.control_values: dict | None = None
        self._listener = None

    def patch(self, owner, attr: str, new):
        """Replace `owner.attr` for this run; `restore()` puts it back."""
        self._patches.append((owner, attr, getattr(owner, attr)))
        setattr(owner, attr, new)

    def restore(self):
        """Undo every patch and stop listening to compile events."""
        for owner, attr, old in reversed(self._patches):
            setattr(owner, attr, old)
        self._patches.clear()
        if self._listener is not None:
            from jax import monitoring
            monitoring.unregister_event_duration_listener(self._listener)
            self._listener = None

    # ---- per-layer readers, and the host spans they read --------------

    def load_readers(self):
        for spec in self.per_layer_specs:
            self.readers[spec["name"]] = load_module(
                BENCH / "metrics" / f"{spec['name']}.py")

    def wraps(self) -> list[tuple[str, str, bool]]:
        out = []
        for mod in self.readers.values():
            for w in getattr(mod, "WRAPS", ()):
                if w not in out:
                    out.append(w)
        return out

    def _timed(self, fn, name: str, block: bool):
        import jax

        def wrapper(*a, **kw):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(name):
                out = fn(*a, **kw)
                if block:
                    jax.block_until_ready(out)
            self.spans.append((name, t0, time.perf_counter()))
            return out
        wrapper.__wrapped__ = fn
        return wrapper

    def instrument(self, instance=None, class_path: str | None = None):
        """Install the readers' host spans.  Module and class attributes
        are wrapped where they live; with `instance`, attributes that the
        class's instances set themselves (`class_path` + attribute)."""
        for dotted, name, block in self.wraps():
            if dotted in self._installed:
                continue
            if instance is not None:
                if not dotted.startswith(class_path + "."):
                    continue
                owner, attr = instance, dotted[len(class_path) + 1:]
                if attr not in vars(instance):
                    continue
            else:
                hit = resolve(dotted)
                if hit is None:
                    continue
                owner, attr = hit
            self.patch(owner, attr, self._timed(getattr(owner, attr), name, block))
            self._installed.add(dotted)

    def missing(self) -> list[str]:
        """Wrapped callables the program no longer has: their metrics read
        nothing and are left out."""
        return [d for d, _, _ in self.wraps() if d not in self._installed]

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span of the benchmark's own, on the profiler's clock."""
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.spans.append((name, t0, time.perf_counter()))

    def in_window(self, name: str) -> list[float]:
        """Durations of the spans `name` that started inside the window."""
        w0, w1 = self.window
        return [b - a for n, a, b in self.spans if n == name and w0 <= a < w1]

    # ---- compile events -----------------------------------------------

    def listen_compiles(self):
        from jax import monitoring

        def on_duration(event, secs, **kw):
            if event in COMPILE_EVENTS:
                self.compiles.append((time.perf_counter(), secs))
        monitoring.register_event_duration_secs_listener(on_duration)
        self._listener = on_duration

    def compile_s_in_window(self) -> float:
        w0, w1 = self.window
        return sum(s for t, s in self.compiles if w0 <= t <= w1)

    # ---- the profiler trace of a steady stretch -----------------------

    def trace_start(self):
        import jax
        self._trace_dir = ROOT / ".bench_trace" / self.cell["name"]
        if self._trace_dir.exists():
            import shutil
            shutil.rmtree(self._trace_dir)
        self._trace_dir.mkdir(parents=True)
        # Host annotations only: the Python tracer would slow the host in
        # the very stretch whose idle time is being read.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self._trace_dir), profiler_options=opts)
        self._trace_t = (time.perf_counter(), None)

    def trace_stop(self):
        import jax
        jax.profiler.stop_trace()
        self._trace_t = (self._trace_t[0], time.perf_counter())
        from bench import trace_reduce
        files = sorted(self._trace_dir.rglob("*.xplane.pb"))
        if not files:
            raise BenchError("the profiler wrote no .xplane.pb")
        names = {n for n, _, _ in self.spans} | {n for _, n, _ in self.wraps()}
        self.trace_result = trace_reduce.reduce_file(files[-1], frozenset(names))

    @property
    def tracing(self) -> bool:
        return self._trace_t is not None and self._trace_t[1] is None


def device_info(chips: int) -> dict:
    """The accelerator as JAX reports it.  No TPU, or fewer chips than the
    cell asks for, ends the run: no number from a CPU is ever reported."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise BenchError(f"JAX found no accelerator: {e}") from None
    if not devs or devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX reports platform "
                         f"{devs[0].platform if devs else None!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak_bytes(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def peaks(kind: str) -> dict:
    """The chip's published peaks; a kind that is not in the table is an
    error, never a default."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def flops_model(config: dict):
    return load_module(BENCH / "flops" / f"{config['model']}.py")


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: dict,
                breakdown: dict | None = None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)


def print_checks(checks: dict):
    """Each compared number beside its limit, as the last lines of stderr."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}",
              file=sys.stderr)
