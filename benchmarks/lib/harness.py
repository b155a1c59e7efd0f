"""What every driver shares: the device check, the measured window with
its compile counter and (with `--trace 1`) its profiler capture, and the
one JSON line a run ends with."""

from __future__ import annotations

import json
import os
import shutil
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from . import peaks, xplane
from .spec import Spec

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
OUT_DIR = ".bench_out"
DEFAULT_TRACE_SECONDS = 6.0


# Contexts of this process's runs, by cell name: how a driver hands its
# context to a loop the program starts on another thread.
CONTEXTS: Dict[str, "Context"] = {}


class NoAccelerator(SystemExit):
    pass


class Context:
    """One run of one cell."""

    def __init__(self, spec: Spec, seed: int, seconds: float, trace: bool,
                 t_start: float, rehearse: bool = False):
        self.spec, self.seed, self.seconds = spec, int(seed), float(seconds)
        self.trace, self.rehearse, self.t_start = trace, rehearse, t_start
        self.out_dir = os.path.join(spec.root, OUT_DIR, spec.name)
        os.makedirs(self.out_dir, exist_ok=True)
        self.t_open = self.t_close = 0.0
        self.setup_s = 0.0
        self._compiles: List[float] = []
        self._lock = threading.Lock()
        self._tracer: Optional[threading.Thread] = None
        self.trace_error: Optional[str] = None
        # The traced stretch of the window, and a driver's counters
        # (`probe`) read at its two ends.
        self.trace_t0 = self.trace_t1 = 0.0
        self.probe = lambda: {}
        self.probe0: Dict[str, Any] = {}
        self.probe1: Dict[str, Any] = {}
        self.notes: Dict[str, Any] = {}

    # -- device ---------------------------------------------------------

    def devices(self):
        """The chips this cell runs on; no fallback to the CPU."""
        import jax

        devs = jax.devices()
        if self.rehearse:
            if len(devs) < self.spec.chips:
                raise NoAccelerator(
                    f"rehearsal needs {self.spec.chips} devices")
            return devs[:self.spec.chips]
        if devs[0].platform != "tpu":
            raise NoAccelerator(
                f"no accelerator: jax found {devs[0].platform!r} devices")
        if len(devs) < self.spec.chips:
            raise NoAccelerator(
                f"cell {self.spec.name} needs {self.spec.chips} chips, "
                f"jax found {len(devs)}")
        peaks.peaks_for(devs[0].device_kind)      # unknown kind: an error
        return devs[:self.spec.chips]

    def install_counters(self) -> None:
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event: str, duration_secs: float, **_kw: Any
                     ) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self._compiles.append(time.monotonic())

    # -- the window -----------------------------------------------------

    def open_window(self) -> float:
        """Set-up ends here. Returns the window's opening time
        (`time.monotonic`)."""
        self.t_open = time.monotonic()
        self.setup_s = self.t_open - self.t_start
        self.t_close = self.t_open + self.seconds
        if self.trace:
            self._tracer = threading.Thread(
                target=self._capture, name="bench-tracer", daemon=True)
            self._tracer.start()
        return self.t_open

    def close_window(self) -> None:
        if self._tracer is not None:
            self._tracer.join(timeout=120)

    def _capture(self) -> None:
        """The profiler runs for a few seconds in the middle of the
        window, from a thread of its own so that the load never waits
        for it. The `bench:window` span marks what the reduction cuts
        to."""
        import jax

        length = min(float(self.spec.sizes.get(
            "trace_seconds", DEFAULT_TRACE_SECONDS)), self.seconds * 0.6)
        logdir = os.path.join(self.out_dir, "trace")
        shutil.rmtree(logdir, ignore_errors=True)
        time.sleep(max(0.0, (self.seconds - length) / 2 - 1.0))
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(logdir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench:window"):
                    self.trace_t0, self.probe0 = time.monotonic(), self.probe()
                    time.sleep(length)
                    self.trace_t1, self.probe1 = time.monotonic(), self.probe()
            finally:
                jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 — reported, run goes on
            self.trace_error = f"{type(e).__name__}: {e}"

    def compiles_in_window(self) -> int:
        with self._lock:
            return sum(self.t_open <= t <= self.t_close
                       for t in self._compiles)

    def reduced_trace(self) -> Optional[xplane.Trace]:
        if not self.trace:
            return None
        path = xplane.find_xplane(os.path.join(self.out_dir, "trace"))
        if path is None:
            return None
        planes = xplane.read_xplane(path)
        self.notes["trace_file_bytes"] = os.path.getsize(path)
        self.notes["trace_planes"] = {
            p: {ln: len(evs) for ln, evs in lines.items()}
            for p, lines in planes.items()}
        tr = xplane.reduce_trace(planes)
        top = sorted(tr.module_s.items(), key=lambda kv: -kv[1])[:12]
        self.notes["trace_modules"] = [
            [n, s, tr.module_n.get(n, 0)] for n, s in top]
        ops = sorted(tr.op_s.items(), key=lambda kv: -kv[1])[:30]
        self.notes["trace_ops"] = [[n, round(s, 6)] for n, s in ops]
        return tr

    def span(self, name: str):
        """A host span on the profiler's clock (free when it is off)."""
        import jax

        return jax.profiler.TraceAnnotation(xplane.SPAN_PREFIX + name)

    def log(self, **kv: Any) -> None:
        """An earlier line of the output: never the last."""
        print(json.dumps(kv, default=str), flush=True)


def device_report(devs, trace: Optional[xplane.Trace]) -> Dict[str, Any]:
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    if trace is not None:
        out["busy_s"] = trace.busy_mean_s
        out["window_s"] = trace.window_s
    return out


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, rehearse: bool = False,
             out=None) -> int:
    """Run one cell and print its result line. Returns the exit code."""
    out = out or sys.stdout
    spec = Spec(root, workload)
    ctx = Context(spec, seed, seconds, trace, t_start, rehearse)
    devs = ctx.devices()
    ctx.install_counters()
    driver = spec.load_module("drivers", spec.traffic["driver"])
    if driver is None:
        raise SystemExit(f"no driver {spec.traffic['driver']!r}")
    result = driver.run(ctx, devs)

    reduced = ctx.reduced_trace()
    values: Dict[str, float] = {}
    if trace:
        measure = dict(result.get("measure", {}))
        measure.update(ctx=ctx, trace=reduced, devices=devs,
                       end_to_end=result["end_to_end"])
        for m in spec.metrics("per_layer"):
            reader = spec.load_module("layer_metrics", m["name"])
            if reader is None:
                continue
            v = reader.read(m, measure)
            if v is not None:
                values[m["name"]] = float(v)
        wanted = spec.metrics("per_layer")
    else:
        e2e = dict(result["end_to_end"], setup_s=ctx.setup_s)
        wanted = spec.metrics("end_to_end")
        values = {m["name"]: float(e2e[m["name"]]) for m in wanted
                  if e2e.get(m["name"]) is not None}
    units = {m["name"]: m["unit"] for m in wanted}
    metrics = {n: {"value": v, "unit": units[n]} for n, v in values.items()}
    line: Dict[str, Any] = {
        "correct": bool(result["correct"]),
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
        "metrics": metrics,
        "device": device_report(devs, reduced),
    }
    if trace and reduced is not None:
        line["breakdown"] = reduced.breakdown()
    ctx.log(workload=workload, seed=seed, seconds=seconds,
            setup_s=ctx.setup_s, compiles_in_window=ctx.compiles_in_window(),
            trace_error=ctx.trace_error, notes=ctx.notes,
            **result.get("info", {}))
    if rehearse:
        # A CPU rehearsal proves control flow. Its numbers are no device
        # metrics and are not printed under their names.
        line["rehearsal"] = metrics
        line["metrics"] = {}
    print(json.dumps(line), file=out, flush=True)
    return 0
