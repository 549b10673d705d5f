"""The benchmark's cell-independent machinery: where its state lives, the
cell's files found by name, the device check, compile counting, host
spans, tracing of the window, the per-layer metric readers and the result
line.

A cell is found by name only: ``BENCHMARK.json`` names its configuration
(``bench/configs/<config>.json``, whose ``reference`` names the plain
reference under ``bench/reference/``), its traffic mix
(``bench/traffic/<traffic>.json``, whose ``kind`` names the generator
under ``bench/drivers/``), its limits (``bench/limits/<cell>.json``) and
its per-layer metrics (``bench/metrics/<metric>.py``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import os
import shutil
import sys
from typing import Any, Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# Fixed paths inside the checkout: the persistent compile cache's key
# includes its directory, so it must never move between runs.
STATE = os.path.join(ROOT, ".bench")
COMPILE_CACHE = os.path.join(STATE, "jax_cache")
AUTOTUNE_CACHE = os.path.join(STATE, "autotune.json")
TRACE_DIR = os.path.join(STATE, "trace")
WINDOW_SPAN = "bench.window"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def prepare_env() -> None:
    """Point every cache at the checkout; call before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = COMPILE_CACHE
    os.environ["REPRO_AUTOTUNE_CACHE"] = AUTOTUNE_CACHE
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for path in (os.path.join(ROOT, "src"), ROOT):
        if path not in sys.path:
            sys.path.insert(0, path)


def configure_jax() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE)
    # cache every program, however fast it compiles, so that set-up after
    # the first run of a cell is loading only
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ---------------------------------------------------------------------------
# cells


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_file: Optional[str] = None) -> Cell:
    spec = _load_json(bench_file or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(os.path.join(ROOT, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(BENCH, "traffic",
                                      w["traffic"] + ".json"))
    limits = _load_json(os.path.join(BENCH, "limits", name + ".json"))
    return Cell(name=name, chips=w["chips"], config=config, traffic=traffic,
                limits=limits,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, name)])


def driver(cell: Cell):
    return importlib.import_module(f"bench.drivers.{cell.traffic['kind']}")


def metric_reader(name: str) -> Callable:
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench.metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# device


def device_info(chips: int) -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise NoChip(f"no TPU: JAX reports platform {d0.platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def device_peaks() -> Dict[str, Any]:
    """The chip's peaks (``bench/peaks.json``); empty off the TPU, where
    no device metric is computed."""
    import jax
    from bench import yardstick
    d0 = jax.devices()[0]
    return yardstick.peaks(d0.device_kind) if d0.platform == "tpu" else {}


def checks(cell: Cell, values: Dict[str, float]) -> Dict[str, Dict]:
    """Each number the cell's limits file names, beside its limit."""
    return {k: {"value": values[k], "limit": c["limit"]}
            for k, c in cell.limits["checks"].items()}


def peak_bytes() -> int:
    import jax
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())


class CompileLog:
    """Counts XLA compiles and persistent-cache hits from JAX's monitoring
    events, and the program's own retrace counters."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> Dict[str, Any]:
        from repro.analysis import retrace
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits,
                "retraces": dict(retrace.counts())}

    @staticmethod
    def delta(a: Dict[str, Any], b: Dict[str, Any]) -> Dict[str, Any]:
        keys = set(a["retraces"]) | set(b["retraces"])
        return {"compiles": b["compiles"] - a["compiles"],
                "compile_s": b["compile_s"] - a["compile_s"],
                "cache_hits": b["cache_hits"] - a["cache_hits"],
                "retraces": sum(b["retraces"].get(k, 0)
                                - a["retraces"].get(k, 0) for k in keys)}


def span(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def traced(enabled: bool, ops: bool = True):
    """Trace the enclosed window with the device tracer on and the Python
    tracer off; yields a holder whose ``summary`` is set on exit (``ops``:
    see ``trace_reduce.summarize``)."""
    holder = type("Traced", (), {"summary": None})()
    if not enabled:
        yield holder
        return
    import jax
    from bench import trace_reduce
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    try:
        yield holder
    finally:
        jax.profiler.stop_trace()
    holder.summary = trace_reduce.load(TRACE_DIR, WINDOW_SPAN, ops)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)


# ---------------------------------------------------------------------------
# result


@dataclasses.dataclass
class Run:
    """What a driver hands back; per-layer readers see this object."""

    cell: Cell
    model: Dict[str, Any]             # the model section as run
    end_to_end: Dict[str, float]
    counters: Dict[str, Any]
    checks: Dict[str, Dict[str, float]]   # name -> {"value", "limit"}
    attempted: int
    failed: int
    window_s: float
    peaks: Dict[str, Any] = dataclasses.field(default_factory=dict)
    trace: Any = None

    @property
    def correct(self) -> bool:
        return all(c["value"] <= c["limit"] for c in self.checks.values()) \
            and self.failed == 0 and bool(self.checks)


def per_layer(run: Run) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in run.cell.per_layer:
        value = metric_reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(run: Run, device: Dict[str, Any], trace: bool
                ) -> Dict[str, Any]:
    if trace:
        metrics = per_layer(run)
    else:
        units = {m["name"]: m["unit"] for m in run.cell.end_to_end}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in run.end_to_end.items() if k in units}
    line: Dict[str, Any] = {
        "correct": run.correct, "attempted": run.attempted,
        "failed": run.failed, "metrics": metrics, "device": dict(device)}
    if trace and run.trace is not None:
        line["device"]["busy_s"] = run.trace.busy_s
        line["device"]["window_s"] = run.trace.window_s
        line["breakdown"] = {"device_ops": run.trace.top_ops(10),
                             "idle_gaps": run.trace.idle_gaps(10)}
    line["checks"] = run.checks
    return line
