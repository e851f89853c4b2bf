"""One run of one cell: set-up, the measured window, the check, the line.

``run_cell`` is everything ``run.py`` does after it has found the chip.
It builds the cell's pool from the seed, builds the engine the
configuration states, warms the cell's own shapes, measures for
``seconds``, then checks what the timed path returned against the plain
reference (``reference.py``) and composes the result line.

The window drives the program's normal path only: one-wave tickets go to
an ``AlignmentEngine(...).stream()`` session (``submit_packed`` /
``poll`` / ``as_completed``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import pathlib
import shutil
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

import reference
import traffic as traffic_mod

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
# where the kernel executable lives: the engine wraps every backend call
# in one jitted function, which the device trace names ``jit__run``.  The
# name is the program's private one (``_Executable``'s ``_run``), as are
# ``engine._cache`` and ``repro.obs.profile._active`` below; a rename
# makes the kernel readers raise rather than go silent
ALIGN_MODULE = r"^jit__run\b"
# reference workers: the window is over, so the host's cores are free
REF_WORKERS = min(8, os.cpu_count() or 1)
# how long past the window's close an answer may still come
GRACE_S = 60.0


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def pen(self):
        c = self.config
        return (int(c["mismatch"]), int(c["gap_open"]), int(c["gap_extend"]))


def load_cell(name: str) -> Cell:
    """Resolve a cell of ``BENCHMARK.json`` to its files."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / cfgs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{w['traffic']}.json").read_text())

    def mine(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if mine(m)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if mine(m) and m["moves"] in moved]
    return Cell(name, config, traffic, int(w["chips"]), e2e, per_layer)


def metric_reader(name: str) -> Callable:
    """``metrics/<name>.py``'s ``read(ctx)``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileLog:
    """Persistent-cache hits and misses and backend compiles, from JAX's
    own monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.hits = self.misses = self.compiles = 0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def count(self) -> int:
        """Every compile or cache read so far."""
        return self.hits + self.misses + self.compiles


def build_engine(cell: Cell):
    from repro.core.engine import AlignmentEngine
    from repro.core.scoring import GapAffine
    c = cell.config
    return AlignmentEngine(
        GapAffine(*cell.pen), backend=c["backend"],
        edit_frac=float(cell.traffic["edit_frac"]),
        chunk_pairs=int(c["wave_pairs"]),
        adaptive=bool(c["adaptive_recovery"]))


def check_compiled(engine) -> List[str]:
    """Every cached kernel executable holds ``tpu_custom_call`` (the
    Pallas kernel was lowered for the chip, not interpreted)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.wfa.ops import default_interpret
    if default_interpret():
        raise RuntimeError("the kernel would run in interpret mode")
    lines = []
    for key, exe in list(engine._cache.items()):
        pshape, tshape = key[3], key[4]
        shapes = [jax.ShapeDtypeStruct(s, jnp.int32)
                  for s in (pshape, tshape, pshape[:1], tshape[:1])]
        if "tpu_custom_call" not in exe.fn.lower(*shapes).as_text():
            raise RuntimeError(f"no tpu_custom_call in the kernel "
                               f"executable {list(pshape)}")
        lines.append(f"kernel executable {list(pshape)} holds "
                     f"tpu_custom_call (interpret=False)")
    return lines


@contextlib.contextmanager
def traced(enabled: bool, out: dict):
    """With ``enabled``: profile the block with ``jax.profiler`` (python
    tracer off, the program's profiler annotations on); ``out`` receives
    the compact trace."""
    if not enabled:
        yield
        return
    import jax
    from repro.obs import profile as obs_profile
    d = tempfile.mkdtemp(prefix="chipbench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    # repro.obs.profile.annotation() emits only while its own profile()
    # block runs, and that block cannot turn the python tracer off
    prev, obs_profile._active = obs_profile._active, True
    try:
        yield
    finally:
        obs_profile._active = prev
        jax.profiler.stop_trace()
        try:
            import tracered
            out["trace"] = tracered.compact(d)
        finally:
            shutil.rmtree(d, ignore_errors=True)


def annotation(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class Marks:
    """The window's edges, with the compile and retrace counts at each."""

    def __init__(self, compiles: CompileLog, engine):
        self.compiles, self.engine = compiles, engine
        self.t_start = self.t_end = 0.0
        self.counts = {}

    def _count(self) -> tuple:
        return self.compiles.count(), self.engine.cache_traces()

    def start(self) -> float:
        self.counts["start"] = self._count()
        self.t_start = time.perf_counter()
        return self.t_start

    def end(self) -> None:
        self.t_end = time.perf_counter()
        self.counts["end"] = self._count()

    @property
    def in_window(self) -> tuple:
        (c0, r0), (c1, r1) = self.counts["start"], self.counts["end"]
        return c1 - c0, r1 - r0


# -- the batch window -------------------------------------------------------

@dataclasses.dataclass
class BatchRun:
    pool_rows: List[np.ndarray]     # per completed ticket: its pool rows
    scores: List[np.ndarray]
    n_submitted: int                # pairs submitted in the window
    t_last: float                   # last completion (perf_counter s)


def batch_window(engine, cell: Cell, pool, seconds: float, heuristic,
                 marks: Marks, ready: Callable, trace_out: dict,
                 trace_on: bool) -> BatchRun:
    P, plen, T, tlen = pool
    wave = int(cell.config["wave_pairs"])
    n_waves = P.shape[0] // wave
    output = cell.traffic["output"]
    sess = engine.stream()

    def submit(w: int):
        lo = w * wave
        return sess.submit_packed(P[lo:lo + wave], plen[lo:lo + wave],
                                  T[lo:lo + wave], tlen[lo:lo + wave],
                                  output=output, heuristic=heuristic)

    # set-up: the window's own shape, through the window's own session
    for w in range(min(2, n_waves)):
        submit(w)
    for t in sess.as_completed():
        t.result()
    ready()

    rows: Dict[int, int] = {}
    done = []

    def collect(tickets):
        # stamp each ticket as it comes: the drain's generator yields them
        # one by one as their waves finish
        for t in tickets:
            done.append((t, time.perf_counter()))

    i = 0
    with traced(trace_on, trace_out), annotation("chipbench.window"):
        t_end = marks.start() + seconds
        while time.perf_counter() < t_end:
            with annotation("chipbench.submit"):
                t = submit(i % n_waves)
            rows[t.index] = i % n_waves
            i += 1
            collect(sess.poll())
        collect(sess.as_completed(timeout=GRACE_S))
        marks.end()
    sess.close()
    out = BatchRun([], [], i * wave,
                   max((t for _, t in done), default=marks.t_start))
    for t, _ in done:
        res = t.result()
        lo = rows[t.index] * wave
        out.pool_rows.append(np.arange(lo, lo + wave))
        out.scores.append(np.asarray(res.scores))
    return out


# -- the check --------------------------------------------------------------

def _reference(pool, rows: np.ndarray, pen) -> np.ndarray:
    """Reference scores of pool ``rows`` (each distinct row once)."""
    P, plen, T, tlen = pool
    uniq, inv = np.unique(rows, return_inverse=True)
    ref = reference.gotoh_scores(P[uniq], plen[uniq], T[uniq], tlen[uniq],
                                 pen, workers=REF_WORKERS)
    return ref[inv]


def check_batch(run: BatchRun, cell: Cell, pool):
    """-> (checks, pairs with a fault)."""
    P, plen, T, tlen = pool
    rows = (np.concatenate(run.pool_rows) if run.pool_rows
            else np.zeros(0, np.int64))
    got = (np.concatenate(run.scores) if run.scores
           else np.zeros(0, np.int32))
    want = _reference(pool, rows, cell.pen)
    bad = got != want
    missing = run.n_submitted - len(rows)
    checks = {"missing_pairs": {"value": int(missing), "limit": 0},
              "wrong_scores": {"value": int(bad.sum()), "limit": 0}}
    return checks, int(missing + bad.sum())


# -- one run ----------------------------------------------------------------

def device_block(chips: int) -> dict:
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs[:max(chips, 1)]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def load_peaks() -> dict:
    return json.loads((HERE / "peaks.json").read_text())


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_proc: float, heuristic=None, require_tpu: bool = True,
             keep_trace: Optional[str] = None) -> dict:
    """One run -> {"line": the result dict, "log": [stderr lines]}.

    ``heuristic`` serves the control only: a pruning heuristic that breaks
    the configuration's exactness.  ``require_tpu=False`` lets the CPU
    tests drive a run with the kernel interpreted.  ``keep_trace`` names
    a file to write the compact trace to.
    """
    log: List[str] = []
    t_enter = time.perf_counter()
    compiles = CompileLog()
    pool = traffic_mod.build_pool(cell.traffic, cell.config, seed)
    t_pool = time.perf_counter()
    log.append(f"pool: {pool[0].shape[0]} pairs from seed {seed} "
               f"(E={cell.traffic['edit_frac']}, "
               f"output={cell.traffic['output']})")
    engine = build_engine(cell)
    marks = Marks(compiles, engine)

    def ready():
        # set-up is done: every shape of the window is warm
        if require_tpu and cell.config["backend"] == "kernel":
            log.extend(check_compiled(engine))
        for exe in engine._cache.values():
            log.append(f"bounds: s_max={exe.s_max} k_max={exe.k_max} "
                       f"k_pad={_round_up(2 * exe.k_max + 1, 128)}")

    trace_out: dict = {}
    run = batch_window(engine, cell, pool, seconds, heuristic, marks, ready,
                       trace_out, trace)
    device = device_block(cell.chips)
    if keep_trace and "trace" in trace_out:
        pathlib.Path(keep_trace).write_text(json.dumps(trace_out["trace"]))
    n_comp, n_retr = marks.in_window
    log.append(f"compiles inside the window: {n_comp} (persistent-cache "
               f"reads and backend compiles), engine retraces: {n_retr}")
    del engine
    checks, failed = check_batch(run, cell, pool)
    n_done = sum(len(sc) for sc in run.scores)
    took = run.t_last - marks.t_start
    e2e = {"pairs_per_s": n_done / took, "setup_s": marks.t_start - t_proc}
    log.append(f"window: {n_done} pairs returned in {took:.3f} s")
    log.append(f"set-up {e2e['setup_s']:.3f} s: process and device start "
               f"{t_enter - t_proc:.3f} s, pool {t_pool - t_enter:.3f} s, "
               f"engine and warm-up {marks.t_start - t_pool:.3f} s")
    line = {"correct": all(c["value"] <= c["limit"]
                           for c in checks.values()),
            "attempted": int(run.n_submitted), "failed": int(failed)}
    if trace:
        ctx = _context(cell, run, pool, trace_out, device, log)
        metrics = {}
        for m in cell.per_layer:
            value = metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        red = ctx["reduction"]
        device = dict(device, busy_s=red.mean_busy_s, window_s=red.window_s)
        line.update(metrics=metrics, device=device,
                    breakdown={"device_ops": red.top_ops(),
                               "idle_gaps": red.top_gaps()})
        log.append(f"traced window {red.window_s:.3f} s, device busy "
                   f"{red.mean_busy_s:.3f} s per device")
    else:
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
        line.update(metrics=metrics, device=device)
    line["checks"] = checks
    for name, c in checks.items():
        log.append(f"check {name}: {c['value']} (limit {c['limit']})")
    return {"line": line, "log": log}


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def _context(cell: Cell, run, pool, trace_out: dict, device: dict,
             log: List[str]) -> dict:
    """What the per-layer readers may read (and ``log``, for lines they
    print)."""
    import tracered
    ctx = {"cell": cell, "device": device, "log": log,
           "reduction": tracered.Reduction(trace_out["trace"]),
           "align_module": ALIGN_MODULE, "peaks": load_peaks()}
    P, plen, T, tlen = pool
    rows = np.concatenate(run.pool_rows)
    ctx["pairs"] = {"plen": plen[rows], "tlen": tlen[rows],
                    "score": np.concatenate(run.scores),
                    "output": cell.traffic["output"], "pen": cell.pen}
    return ctx
