"""The benchmark's run: set-up, the first steps the correctness check
follows, the measured window, the optional trace, and the comparison with
the plain reference.

Everything a cell needs is found by name, in files of its own:
``configs/<config>.json``, ``traffic/<mix>.json`` (which names the path it
drives), ``paths/<path>.py``, ``metrics/<metric>.py`` and
``limits/<workload>.json``.  Adding a cell, a configuration or a per-layer
metric adds files and entries; no file here changes.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
#: the first steps, driven through the window's own loop, that the plain
#: reference follows
CHECKED_STEPS = 3
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


# --------------------------------------------------------------------------- #
# Discovery by name
# --------------------------------------------------------------------------- #
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def workload(name: str, bench: dict) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return load_json(HERE / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(HERE / "traffic" / f"{name}.json")


def limits(workload_name: str) -> dict:
    return load_json(HERE / "limits" / f"{workload_name}.json")


def _module(kind: str, name: str):
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} {name!r} ({path})")
    mod_name = f"benchmarks.chip.{kind}.{name.replace('.', '_')}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def path_module(name: str):
    """``paths/<name>.py``: the system under test, as the cell drives it."""
    return _module("paths", name)


def metric_reader(name: str):
    """``metrics/<name>.py``: ``read(run) -> value or None``."""
    return _module("metrics", name)


def peak(device_kind: str) -> dict:
    """The published peaks of one chip of this kind; an unknown kind is
    an error, never a default."""
    table = load_json(HERE / "peaks.json")
    if device_kind not in table or device_kind == "source":
        known = sorted(k for k in table if k != "source")
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"peaks.json ({known})")
    return table[device_kind]


def per_layer(bench: dict, name: str) -> list:
    """The per-layer metrics that this workload reports."""
    cell_e2e = {m["name"] for m in bench["end_to_end"]
                if name in m.get("workloads", [name])}
    return [m for m in bench["per_layer"]
            if name in m.get("workloads", [name]) and m["moves"] in cell_e2e]


# --------------------------------------------------------------------------- #
# Host spans
# --------------------------------------------------------------------------- #
class Spans:
    """The benchmark's own host spans, on the host clock, and in the
    profiler's trace while one is taken."""

    def __init__(self):
        self.records: list = []
        self.tracing = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.tracing:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def total(self, name: str, t0: float, t1: float) -> float:
        """Seconds spent in ``name`` spans that started in [t0, t1]."""
        return sum(b - a for n, a, b in self.records
                   if n == name and t0 <= a <= t1)


class CompileCounter:
    """Counts XLA compilations (persistent-cache loads included)."""

    def __init__(self):
        import jax
        self.n = 0

        def listen(event, duration, **kw):
            if event == BACKEND_COMPILE:
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(listen)
        self._listen = listen

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listen)


# --------------------------------------------------------------------------- #
# Correctness: the program's first steps against the plain reference
# --------------------------------------------------------------------------- #
def _worst(gaps) -> float:
    """The largest gap; infinite where any reading is not finite (``max``
    would pass over a NaN)."""
    gaps = list(gaps)
    return max(gaps) if all(map(math.isfinite, gaps)) else math.inf


def _gap(prog: dict, ref: dict, keys, floor: float) -> float:
    return _worst(abs(prog[k] - ref[k]) / max(ref[k], floor) for k in keys)


def _norm(x) -> float:
    return float(np.linalg.norm(np.asarray(x, np.float64)))


def compare(prog: dict, ref: dict, lim: dict) -> dict:
    """The numbers that ``lim`` names, each with its limit.

    * ``first_loss_gap``: the relative gap of the first step's loss;
    * ``loss_gap``: the largest relative gap of any checked step's loss
      (the later steps carry Adam's amplification of round-off -- sign
      flips of near-zero gradient elements move them by a full step -- so
      a cell compares it only where something separates it);
    * ``grad_gap``: the worst leaf's gap between the norms of the first
      gradient, against the reference's norm of that leaf or of the median
      leaf, whichever is larger;
    * ``grad_diff``: the worst leaf's norm of the difference between the
      two first gradients, against the same norm (a gap between norms
      moves only at second order with errors that average out, as a lower
      precision's do; this moves at first order);
    * ``update_gap``: the same for the change of the parameters over the
      checked steps, leaving out leaves whose reference gradient is under a
      thousandth of the median leaf's (they move by round-off alone)."""
    if set(prog["grad"]) != set(ref["grad"]) or \
            set(prog["update"]) != set(ref["update"]):
        raise ValueError("the program's parameter tree differs from the "
                         "reference's")
    losses = [abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"],
                                                  strict=True)]
    g_ref = {k: _norm(g) for k, g in ref["grad"].items()}
    g_prog = {k: _norm(g) for k, g in prog["grad"].items()}
    g_med = statistics.median(g_ref.values())
    moved = [k for k in ref["update"] if g_ref[k] >= 1e-3 * g_med]
    u_med = statistics.median(ref["update"][k] for k in moved)
    values = {"first_loss_gap": _worst(losses[:1]),
              "loss_gap": _worst(losses),
              "grad_gap": _gap(g_prog, g_ref, g_ref, g_med),
              "grad_diff": _worst(
                  _norm(prog["grad"][k] - ref["grad"][k]) / max(g, g_med)
                  for k, g in g_ref.items()),
              "update_gap": _gap(prog["update"], ref["update"], moved,
                                 u_med)}
    return {k: {"value": values[k], "limit": lim[k]} for k in lim}


def passed(checks: dict) -> bool:
    # NaN compares false, so a non-finite reading fails
    return all(c["value"] <= c["limit"] for c in checks.values())


# --------------------------------------------------------------------------- #
# The run
# --------------------------------------------------------------------------- #
def drive(job, spans: Spans, first: int, nxt, done, on_step=None):
    """Steps ``first, first + 1, ...`` from the prepared batch ``nxt`` of
    step ``first`` until ``done(i, t)``: each step's batch is prepared on
    the host while the step before it runs, then the loop waits for the
    step.  Returns ([(step, completion time, loss)], the next step's
    prepared batch)."""
    out = []
    i = first
    while True:
        with spans("dispatch"):
            handle = job.dispatch(nxt, i)
        nxt = job.prepare(i + 1)
        with spans("wait"):
            loss = job.wait(handle)
        t = time.perf_counter()
        out.append((i, t, loss))
        if on_step is not None:
            on_step(i, handle)
        if done(i, t):
            return out, nxt
        i += 1


def first_steps(job, spans: Spans) -> dict:
    """Set-up's steps 0 .. CHECKED_STEPS-1, through the window's loop,
    with the readings the reference is compared on (and, under ``next``,
    the prepared batch of the step after them)."""
    readings: dict = {}

    def on_step(i, handle):
        if i == 0:
            readings["grad"] = job.first_grads(handle)
        if i == CHECKED_STEPS - 1:
            readings["update"] = job.update_norms()

    steps, readings["next"] = drive(job, spans, 0, job.prepare(0),
                                    lambda i, t: i == CHECKED_STEPS - 1,
                                    on_step)
    readings["loss"] = [loss for _, _, loss in steps]
    return readings


def quantile(xs, q: float) -> float:
    """Linear-interpolated q-quantile (numpy's default)."""
    xs = sorted(xs)
    pos = (len(xs) - 1) * q
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


class Run:
    """What a run measured, for the per-layer metric readers."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def run(name: str, seed: int, seconds: float, trace: bool, *,
        root: Path = ROOT, log=print) -> dict:
    """One run of workload ``name``; returns the result object."""
    import jax

    t0 = time.perf_counter()
    bench = benchmark(root)
    cell = workload(name, bench)
    cfg, tr = config(cell["config"]), traffic(cell["traffic"])
    lim = limits(name)
    devices = jax.devices()[:cell["chips"]]
    pk = peak(devices[0].device_kind)
    spans = Spans()
    compiles = CompileCounter()
    job = path_module(tr["path"]).Job(cfg, tr, seed, devices, spans)
    prog = first_steps(job, spans)
    setup_s = time.perf_counter() - t0
    log(f"set-up {setup_s:.3f} s, first losses {prog['loss']}")

    first, nxt = CHECKED_STEPS, prog.pop("next")
    tmp = None
    if trace:
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tmp)
        spans.tracing = True
        # the profiler stalls the first step it sees: keep it out of the
        # traced window
        _, nxt = drive(job, spans, first, nxt, lambda i, t: True)
        first += 1
    n_compiles = compiles.n
    start = time.perf_counter()
    with spans("window"):
        steps, _ = drive(job, spans, first, nxt,
                         lambda i, t: t - start >= seconds)
    compiled_in_window = compiles.n - n_compiles
    compiles.close()
    if trace:
        spans.tracing = False
        jax.profiler.stop_trace()
    end = steps[-1][1]
    times = [b - a for a, b in zip([start] + [t for _, t, _ in steps[:-1]],
                                   [t for _, t, _ in steps])]
    window_s = end - start
    failed = sum(not math.isfinite(loss) for _, _, loss in steps)
    log(f"window {window_s:.3f} s, {len(steps)} steps, compilations in the "
        f"window {compiled_in_window}, step times min/median/max "
        f"{min(times)!r} {statistics.median(times)!r} {max(times)!r}")
    if compiled_in_window:
        raise RuntimeError(f"{compiled_in_window} compilations inside the "
                           "measured window")
    # the CPU backend (in tests) keeps no memory statistics
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes}
    result = {"correct": False, "attempted": len(steps), "failed": failed,
              "metrics": {}, "device": device}
    if trace:
        from . import trace as tr_mod
        tdata = tr_mod.load(tmp)
        shutil.rmtree(tmp, ignore_errors=True)
        device["busy_s"] = tdata.busy_s()
        device["window_s"] = tdata.window_ns() / 1e9
        result["breakdown"] = {"device_ops": tdata.top_ops(),
                               "idle_gaps": tdata.idle_gaps()}
        record = Run(job=job, spans=spans, steps=steps, step_times=times,
                     window=(start, end), window_s=window_s, trace=tdata,
                     peak=pk, chips=len(devices), peak_bytes=peak_bytes,
                     cfg=cfg, traffic=tr, log=log)
        for m in per_layer(bench, name):
            value = metric_reader(m["name"]).read(record)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
    else:
        tokens = len(steps) * job.tokens_per_step
        values = {"tokens_per_s": tokens / window_s,
                  "step_s_p90": quantile(times, 0.9), "setup_s": setup_s}
        for m in bench["end_to_end"]:
            if name in m.get("workloads", [name]):
                result["metrics"][m["name"]] = {"value": values[m["name"]],
                                                "unit": m["unit"]}

    job.free()
    del job
    gc.collect()
    ref = path_module(tr["path"]).reference(cfg, tr, seed)
    checks = compare(prog, ref, lim)
    result["correct"] = passed(checks) and failed == 0
    result["checks"] = checks
    return result
