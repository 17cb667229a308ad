"""Run one benchmark cell once: set-up, one measured window, the check.

Everything that belongs to one cell is found by name from
``BENCHMARK.json``:

* ``configs/<config>.json``: the deployment's sizes, and beside it
  ``configs/<config>.py``, the plain reference the check compares with;
* ``traffic/<traffic>.json``: the mix's parameters, whose ``driver`` key
  names the general generator ``drivers/<driver>.py`` that reads them;
* ``metrics/<metric>.py``: one reader per per-layer metric, or, where a
  quantity is split by the end-to-end metric it moves (``<stem>.<part>``),
  the reader ``metrics/<stem>.py`` they share.

A driver module defines ``Driver(run)`` with ``setup()``, ``unit(i)`` (one
unit of work, finished on the host when it returns; returns the work it
did), ``end_to_end(window_s, units, work)``, ``release()`` and ``check()``.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from collections import Counter
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

import xplane

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
#: the fixed in-checkout compile cache (listed in ``.gitignore``)
CACHE_DIR = HERE / ".jax_cache"
#: host spans the harness writes around calls into each layer
SPANS = ("estimate", "resolve", "query", "program", "reference")
#: the event JAX records for every backend compile (a persistent-cache
#: load included): any such event inside the window is a compile there
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """Import one file of the benchmark by its path (names may hold
    ``-`` and ``.``, which ``import`` cannot spell)."""
    name = "bench_" + "_".join(path.relative_to(HERE).with_suffix("").parts)
    name = name.replace("-", "_").replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def by_name(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def metric_reader(name: str) -> ModuleType:
    """``metrics/<name>.py``, else the reader of its stem (the name up to
    its first ``.``)."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.is_file():
        path = HERE / "metrics" / f"{name.split('.', 1)[0]}.py"
    return load_module(path)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclass
class Cell:
    """One ``workloads`` entry with everything it names, loaded."""
    name: str
    chips: int
    config: dict
    traffic: dict
    reference: ModuleType
    driver: ModuleType
    end_to_end: list[dict]
    per_layer: list[dict]


def load_cell(name: str, overrides: dict | None = None) -> Cell:
    """Find a cell's configuration, traffic, driver and reference by name.

    ``overrides`` (tests only) updates the configuration and traffic
    dicts, e.g. to run a cell at a size a CPU test can hold, and may
    give the ``workload`` entry of a cell not (yet) in the manifest."""
    m = load_json(ROOT / "BENCHMARK.json")
    overrides = overrides or {}
    w = overrides.get("workload") or by_name(m["workloads"], name,
                                             "workload")
    c = by_name(m["configs"], w["config"], "config")
    config = load_json(ROOT / c["file"]) | overrides.get("config", {})
    traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json") \
        | overrides.get("traffic", {})
    reference = load_module((ROOT / c["file"]).with_suffix(".py"))
    driver = load_module(HERE / "drivers" / f"{traffic['driver']}.py")
    e2e = [x for x in m["end_to_end"] if applies(x, name)]
    reported = {x["name"] for x in e2e}
    layer = [x for x in m["per_layer"]
             if (name in x["workloads"] if "workloads" in x
                 else x["moves"] in reported)]
    return Cell(name, w["chips"], config, traffic, reference, driver, e2e,
                layer)


@dataclass
class Check:
    """One number compared with its limit: ``ok`` when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


class Instrument:
    """Harness spans and kernel counters around calls into the program.

    Spans go into the profiler's trace (``TraceAnnotation``) and, inside
    the window, their host seconds are summed.  Kernel interface bytes are
    counted inside the window only, so they cover the same calls as the
    traced window.
    """

    def __init__(self):
        self.in_window = False
        self.span_s: Counter = Counter()
        self.kernel_bytes: Counter = Counter()
        self._undo: list[tuple[object, str, object]] = []

    def span(self, name: str):
        return _Span(self, name)

    def wrap(self, owner, attr: str, *, span: str | None = None,
             kernel: str | None = None,
             nbytes=None, to_host: bool = False, on_call=None, inner=None):
        """Replace ``owner.attr`` by a wrapper that opens ``span`` (if
        given) and counts ``kernel``'s interface bytes (if given).

        ``to_host`` copies the result to a numpy array inside the span
        (for an entry whose caller does so at once: the span then ends
        when the result is on the host).  ``on_call(args, kwargs, out)``
        sees every call; ``inner`` replaces the wrapped callable (the
        control)."""
        import numpy as np
        original = getattr(owner, attr)
        fn = original if inner is None else inner

        def wrapped(*args, **kwargs):
            with self.span(span) if span else contextlib.nullcontext():
                out = fn(*args, **kwargs)
                if to_host:
                    out = np.asarray(out)
            if self.in_window and kernel is not None:
                self.kernel_bytes[kernel] += nbytes(*args, **kwargs)
            if on_call is not None:
                on_call(args, kwargs, out)
            return out

        setattr(owner, attr, wrapped)
        self._undo.append((owner, attr, original))

    def restore(self) -> None:
        for owner, attr, original in reversed(self._undo):
            setattr(owner, attr, original)
        self._undo.clear()


class _Span:
    def __init__(self, inst: Instrument, name: str):
        import jax
        self.inst, self.name = inst, name
        self.annotation = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self.annotation.__exit__(*exc)
        if self.inst.in_window:
            self.inst.span_s[self.name] += time.perf_counter() - self.t0
        return False


class CompileMonitor:
    """Counts backend compiles, and persistent-cache hits and requests."""

    def __init__(self):
        self.in_window = False
        self.compiles = 0
        self.compiles_in_window = 0
        self.cache_hits = 0
        self.cache_requests = 0

    def _on_duration(self, event: str, _secs: float, **_kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.compiles += 1
            self.compiles_in_window += self.in_window

    def _on_event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1
        elif event == CACHE_REQUEST_EVENT:
            self.cache_requests += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
        return False


@dataclass
class Run:
    """What a driver is handed: the cell's data, the seed, the spans."""
    cell: Cell
    seed: int
    inst: Instrument
    variant: str = "program"

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    @property
    def reference(self) -> ModuleType:
        return self.cell.reference


@dataclass
class Readings:
    """What a per-layer reader reads: one traced window's numbers."""
    trace: xplane.TraceSummary | None
    window_s: float
    span_s: dict
    kernel_bytes: dict
    compiles_in_window: int
    peaks: dict


def enable_compile_cache() -> None:
    """Keep every compile in the fixed in-checkout cache directory.

    It overrides ``JAX_COMPILATION_CACHE_DIR`` on purpose: the cache then
    lives inside the checkout at a path that never moves, so the first run
    of a cell there fills it, later runs there hit it, and two checkouts
    share nothing.  The write threshold is 0 s, since each of the sense-amp
    kernel's compiles takes under a second."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(require_chips: int | None) -> dict:
    """Platform, kind and count as JAX reports them; raises
    :class:`NoChip` when ``require_chips`` TPUs are not there."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_chips is not None and (info["platform"] != "tpu"
                                      or info["count"] < require_chips):
        raise NoChip(f"this cell needs {require_chips} TPU chip(s); JAX "
                     f"finds {info['count']} {info['platform']} device(s)")
    return info


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def load_peaks(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def run_window(driver, seconds: float, inst: Instrument,
               monitor: CompileMonitor) -> tuple[float, int, float]:
    """Run whole units until ``seconds`` have passed; -> (window seconds,
    units, work).  The window closes when the last unit has finished."""
    import jax
    units, work = 0, 0.0
    inst.in_window = monitor.in_window = True
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
        while True:
            work += driver.unit(units)
            units += 1
            if time.perf_counter() - t0 >= seconds:
                break
    window_s = time.perf_counter() - t0
    inst.in_window = monitor.in_window = False
    return window_s, units, work


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             variant: str = "program", require_chips: bool = True,
             overrides: dict | None = None) -> dict:
    """Run one cell once and return its result line as a dict.

    ``variant="control"`` puts the cell's control in the program's place
    (``control.py``); the benchmark's own runs never do.
    ``require_chips=False`` (tests) skips the look for a chip."""
    import jax
    cell = load_cell(name, overrides)
    dev = device_info(cell.chips if require_chips else None)
    peaks = load_peaks(dev["kind"]) if require_chips else {}
    inst = Instrument()
    run = Run(cell, seed, inst, variant)
    driver = cell.driver.Driver(run)
    with CompileMonitor() as monitor:
        try:
            driver.setup()
            setup_s = process_age_s()
            log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace \
                else None
            if trace:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(log_dir, profiler_options=opts)
            try:
                window_s, units, work = run_window(driver, seconds, inst,
                                                   monitor)
            finally:
                if trace:
                    jax.profiler.stop_trace()
            dev["memory_peak_bytes"] = memory_peak_bytes()
            summary = None
            if trace:
                summary = xplane.read_xplane(xplane.find_xplane(log_dir),
                                             SPANS)
                shutil.rmtree(log_dir, ignore_errors=True)
            e2e = driver.end_to_end(window_s, units, work)
            driver.release()
            with inst.span("reference"):
                checks = driver.check()
        finally:
            inst.restore()
    cache = {"cache_hits": monitor.cache_hits,
             "cache_requests": monitor.cache_requests,
             "compiles": monitor.compiles,
             "compiles_in_window": monitor.compiles_in_window}
    metrics = {}
    if trace:
        readings = Readings(summary, window_s, dict(inst.span_s),
                            dict(inst.kernel_bytes),
                            monitor.compiles_in_window, peaks)
        for m in cell.per_layer:
            value = metric_reader(m["name"]).read(readings)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
    else:
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    failed = sum(not c.ok for c in checks)
    result = {"correct": failed == 0 and bool(checks), "attempted": units,
              "failed": failed, "metrics": metrics, "device": dev}
    if trace:
        result["breakdown"] = summary.breakdown()
    result["compile_cache"] = cache
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def print_result(result: dict) -> None:
    """Compile-cache line, the checks on standard error, then the result
    line (with ``checks`` as its last key) on standard output."""
    result = dict(result)
    cache = result.pop("compile_cache")
    print(json.dumps({"compile_cache": cache}), flush=True)
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
