"""One run of one cell: set-up, the measured window, the comparison that
decides ``correct``, and the result line.

Set-up makes the cell's data and initial parameters from the seed,
builds the program's training step with its state (``models/<model>``)
on the cell's chips, the first ``chips`` devices JAX lists, and drives
it through the first ``check.STEPS`` steps with the window's own call
and feed, keeping their readings. That compiles, or loads from the
compile cache, every program the window runs. The window then
dispatches step i, waits for step i-1, and stops dispatching once
``seconds`` have passed; it closes when its last step completes. After
it the memory peak of the fullest of the cell's chips is read (the live
arrays' peak plus the largest temporaries of the programs the window
runs), the program's state is freed, and the plain reference
(``reference/<model>``) follows the same first steps from the same
parameters on the same rows.

``--trace 1`` runs the same window under the profiler, with the
benchmark's spans around its calls into the program, and reports the
cell's per-layer metrics in place of its end-to-end ones.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

import jax

from perfbench import check, trace_reduce
from perfbench.feed import Feed

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = pathlib.Path(__file__).resolve().parent


class SetupError(Exception):
    """The run cannot start: unknown cell, missing file, wrong device."""


# ---------------------------------------------------------------------------
# what the cell is, found by name
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    traffic: dict
    limits: dict
    model: Any
    reference: Any
    metrics: List[dict]   # BENCHMARK.json entries this run reports
    readers: Dict[str, Any]


def _json(path: pathlib.Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SetupError(f"missing {path}") from None


def _module(path: pathlib.Path, name: str):
    """Load ``path`` as module ``name`` (metric readers are named after
    their metric, which may hold dots)."""
    if not path.is_file():
        raise SetupError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The ``end_to_end`` (trace off) or ``per_layer`` (trace on) entries
    that ``cell`` reports: those that list it under ``workloads``, and
    those without the key. A per-layer metric without it is reported
    where the end-to-end metric it moves is."""
    def listed(m: dict) -> bool:
        return cell in m.get("workloads", [cell])

    e2e = [m for m in bench["end_to_end"] if listed(m)]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def load_cell(root: pathlib.Path, name: str, trace: bool) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SetupError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = _json(root / configs[w["config"]]["file"])
    base = root / "perfbench"
    model = importlib.import_module(f"perfbench.models.{cfg['model']}")
    reference = importlib.import_module(f"perfbench.reference.{cfg['model']}")
    metrics = metrics_for(bench, name, trace)
    readers = {m["name"]: _module(base / "metrics" / f"{m['name']}.py",
                                  f"perfbench_metric_{m['name']}")
               for m in metrics}
    return Cell(name, int(w["chips"]), cfg,
                _json(base / "traffic" / f"{w['traffic']}.json"),
                _json(base / "cells" / f"{name}.json")["limits"],
                model, reference, metrics, readers)


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise SetupError(f"device kind {kind!r} is not in perfbench/peaks.json "
                         f"(have {sorted(table)})")
    return table[kind]


# ---------------------------------------------------------------------------
# spans and counters
# ---------------------------------------------------------------------------


class Spans:
    """The benchmark's host spans around its calls into the program: on,
    each is a ``TraceAnnotation`` named ``bench/<name>`` in the profiler's
    trace; off, a no-op."""

    def __init__(self, on: bool):
        self.on = on
        self._off = contextlib.nullcontext()

    def __call__(self, name: str):
        if not self.on:
            return self._off
        return jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + name)


class CompileCounter:
    """XLA executables compiled or loaded from the compile cache while
    it is entered (JAX's ``backend_compile_duration`` event)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0

    def _listen(self, event: str, duration: float, **kwargs) -> None:
        if event == self.EVENT:
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._listen)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Context:
    """What a metric reader reads (``metrics/<name>.py``: ``read(ctx)``
    returns the value, or None when it finds nothing to read). ``chips``
    is the number of chips the cell ran on; ``work`` is
    ``models/<model>.work``: the whole step's ``flops``, and the kernel
    calls that each chip makes."""

    cell: Cell
    chips: int
    work: dict
    steps: int
    window_s: float
    setup_s: float
    memory_peak_bytes: Optional[int]
    compiles: int
    trace: Optional[trace_reduce.Reduced]
    peaks: Optional[dict]


def _step_temp_bytes(programs) -> int:
    """The largest temporary allocation among ``programs``: the scratch
    that XLA's buffer assignment gives an execution on top of its
    arguments and outputs."""
    return max((e.get_compiled_memory_stats().temp_size_in_bytes
                for e in programs), default=0)


def _memory_peak(devices, programs) -> Optional[int]:
    """Peak device memory of the fullest chip: the runtime's peak of live
    arrays (``peak_bytes_in_use``, which on the TPU runtime leaves out an
    execution's temporaries) plus the largest temporaries of the programs
    the window runs, which are allocated while a step runs beside the
    arrays live then."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) + _step_temp_bytes(programs) if peaks else None


def _finite(x):
    return x if isinstance(x, (int, float)) and math.isfinite(x) else None


def first_steps(cell: Cell, feed: Feed, inputs: dict, spans: Spans, devices):
    """Build the program's step with its state on ``devices`` and drive
    it through the first ``check.STEPS`` steps with the window's own call
    and feed. Returns the trainer, to be handed on, and the program's
    readings."""
    trainer = cell.model.Trainer(cell.cfg, feed, inputs, spans, devices)
    states, losses = [trainer.state()], []
    for i in range(check.STEPS):
        out = jax.block_until_ready(trainer.step(i))
        losses.append(trainer.loss(out))
        states.append(trainer.state())
    return trainer, cell.model.readings(
        cell.cfg, feed, (states[0], states[1], states[-1]), losses)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: pathlib.Path = ROOT, t_start: Optional[float] = None,
        check_device: bool = True, out=None, err=None) -> int:
    """One run; prints the result line and returns the exit code.
    ``check_device=False`` skips the look for an accelerator and the peak
    table (for tests on the CPU); JAX has to list as many devices as the
    cell has chips either way."""
    out = out or sys.stdout
    err = err or sys.stderr
    t_start = time.perf_counter() if t_start is None else t_start
    try:
        cell = load_cell(root, workload, trace)
        devices = jax.devices()
        dev = devices[0]
        if check_device and dev.platform == "cpu":
            raise SetupError("needs an accelerator; JAX found only the CPU")
        if len(devices) < cell.chips:
            raise SetupError(f"{workload} needs {cell.chips} chips; JAX "
                             f"sees {len(devices)}")
        peaks = peaks_for(dev.device_kind) if check_device else None
    except SetupError as e:
        print(f"perfbench: {e}", file=err)
        return 2
    # the chips the cell runs on: the model is given these, and the
    # memory peak is read on these
    used = devices[:cell.chips]

    spans = Spans(bool(trace))
    model = cell.model
    feed = Feed(cell.traffic, model.rows(cell.cfg), seed)
    inputs = model.make_inputs(cell.cfg, feed, seed, used)
    client = dev.client
    known = client.live_executables()  # held, so that no id is reused
    seen = {id(e) for e in known}
    trainer, prog = first_steps(cell, feed, inputs, spans, used)
    # the programs the first steps compiled or loaded: those the window runs
    programs = [e for e in client.live_executables() if id(e) not in seen]
    del known, seen
    setup_s = time.perf_counter() - t_start

    tdir = tempfile.mkdtemp(prefix="perfbench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    window_losses = []
    with CompileCounter() as compiles, spans("window"):
        t0 = time.perf_counter()
        i, prev = check.STEPS, None
        while True:
            cur = trainer.step(i)
            i += 1
            if prev is not None:
                with spans("wait"):
                    jax.block_until_ready(prev)
                window_losses.append(trainer.loss(prev))
            prev = cur
            if time.perf_counter() - t0 >= seconds:
                break
        with spans("wait"):
            jax.block_until_ready(prev)
        window_losses.append(trainer.loss(prev))
        window_s = time.perf_counter() - t0
    del prev, cur
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        files = sorted(pathlib.Path(tdir).rglob("*.xplane.pb"))
        reduced = trace_reduce.load(files[-1])
        shutil.rmtree(tdir, ignore_errors=True)
    steps = len(window_losses)
    memory_peak = _memory_peak(used, programs)
    del programs
    failed = sum(1 for v in jax.device_get(window_losses)
                 if not math.isfinite(float(v)))
    del trainer, window_losses
    gc.collect()

    ref = cell.reference.run(cell.cfg, inputs)
    correct, checks = check.judge(check.compare(prog, ref), cell.limits)

    ctx = Context(cell, len(used), model.work(cell.cfg, feed), steps, window_s,
                  setup_s, memory_peak, compiles.count, reduced, peaks)
    metrics = {}
    for m in cell.metrics:
        v = cell.readers[m["name"]].read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(used), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": steps, "failed": failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = trace_reduce.busy_s(reduced)
        device["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": trace_reduce.top_ops(reduced),
                               "idle_gaps": trace_reduce.idle_gaps(reduced)}
    result["checks"] = {k: {"value": _finite(c["value"]), "limit": c["limit"]}
                        for k, c in checks.items()}
    for k, c in checks.items():
        print(f"check {k}: {c['value']:.6e} limit {c['limit']:.6e} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}", file=err)
    err.flush()
    print(json.dumps(result), file=out)
    out.flush()
    return 0
