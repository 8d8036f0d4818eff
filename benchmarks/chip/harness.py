"""Run one benchmark cell: a configuration stepped under a traffic mix.

Everything that belongs to one configuration, traffic mix or metric is a file
of its own, found by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>/``: ``config.json`` (sizes, work counts, outputs),
  ``config.py`` (the program's entry point, the estimator's pick and
  candidates, and the seeded fields), ``ref.py`` (the plain reference)
  and ``limits.json`` (the limit of each number ``correct`` compares);
* ``traffic/<traffic>.json``: domain shape and number of domains;
* ``metrics/<metric>.py``: ``read(run)`` -> a number, or ``None`` where the
  run holds nothing for it to read.  A quantity split by the end-to-end
  metric it moves (``idle_share.ensemble``) is read by the file of its part
  before the first dot (``idle_share.py``) unless it has a file of its own.

The time loop is one general generator.  A step makes one call of the
configuration's entry point per domain, in a fixed order, and the output of
step n is the input of step n + 1.  After enqueueing step n the host waits for
step n - 1, so the device always has a step queued.  The window ends when the
last step's outputs are ready; it counts every step it enqueued.

``correct`` compares, against the reference applied to the same input, the
output of sampled steps: a slab of a few rows of an early step of several
domains, drawn from the seed, and the whole of the last step of every domain.
The kernels leave a shell of ``halo`` cells in z and y undefined, so the
comparison leaves that shell out.
"""
from __future__ import annotations

import importlib.util
import json
import math
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

EARLY_SAMPLES = 8  # domains checked at an early step
EARLY_STEPS = 4  # the early step is drawn from the first this many of the window
SLAB_BYTES = 1 << 30  # at most this much of one domain's input per checked block
EARLY_SLAB_BYTES = 32 << 20  # at most this much of one domain's input per early sample
LADDER_MIN_CALLS = 5  # calls of each candidate block in the traced ladder, at least
LADDER_FLOOR_S = 0.02  # and enough calls for about this much kernel time at the pick's speed
WARM_STEPS = 2  # a step whose outputs differ in type from its inputs meets both
WINDOW, ENQUEUE, WAIT = "window", "enqueue", "wait"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclass
class Cell:
    name: str
    chips: int
    config: object  # the configuration's config.py
    spec: dict  # its config.json
    ref: object  # its ref.py
    traffic: dict
    limits: dict[str, float]
    end_to_end: list[tuple[dict, object]]  # (BENCHMARK.json entry, reader module)
    per_layer: list[tuple[dict, object]]


def resolve(root: Path, workload: str) -> Cell:
    """Assemble ``workload`` from ``root``/BENCHMARK.json and the files it names."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    base = root / bench["paths"][0]
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise ValueError(f"no workload {workload!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[workload]
    (c,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    cdir = (root / c["file"]).parent
    tag = c["name"].replace("-", "_").replace(".", "_")

    def readers(kind: str):
        out = []
        for m in bench[kind]:
            if workload in m.get("workloads", [workload]):
                name = m["name"]
                path = base / "metrics" / f"{name}.py"
                if not path.exists():
                    path = base / "metrics" / f"{name.split('.')[0]}.py"
                out.append((m, load_module(path, "metric_" + name.replace(".", "_"))))
        return out

    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=load_module(cdir / "config.py", f"config_{tag}"),
        spec=json.loads((root / c["file"]).read_text()),
        ref=load_module(cdir / "ref.py", f"ref_{tag}"),
        traffic=json.loads((base / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads((cdir / "limits.json").read_text()),
        end_to_end=readers("end_to_end"),
        per_layer=readers("per_layer"),
    )


def peak_for(kind: str, base: Path) -> dict:
    """The peak table's entry for ``device_kind``; an unknown chip is an error."""
    table = json.loads((base / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"device_kind {kind!r} is not in the peak table ({sorted(table)})")
    return table[kind]


def work(spec: dict, shape) -> tuple[float, float]:
    """Compulsory (bytes, FLOPs) of one step of one domain."""
    cells = math.prod(shape)
    return float(cells * spec["bytes_per_cell"]), float(cells * spec["flops_per_cell"])


def roofline_s(peak: dict, nbytes: float, flops: float) -> float:
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def seed_key(seed: int):
    """A PRNG key from any whole number, all its bits used."""
    import jax

    state = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(state, impl="threefry2x32")


@dataclass
class Run:
    """What one run measured; the metric readers read this."""

    cell: Cell
    shape: tuple[int, int, int]
    domains: int
    steps: int = 0
    window_s: float = 0.0
    setup_s: float = 0.0
    select_s: float = 0.0
    pick: tuple | None = None
    peak: dict | None = None
    trace: object = None  # trace_reduce.Window of the measured window
    ladder: dict = field(default_factory=dict)  # block -> kernel seconds per call

    @property
    def work(self) -> tuple[float, float]:
        return work(self.cell.spec, self.shape)

    def kernel_seconds(self) -> float:
        """Device time of the configuration's Pallas kernel in the traced window."""
        from trace_reduce import matching

        return matching(self.trace.op_seconds, self.cell.spec["kernel_pattern"])


def kernel_roofline(run: Run, kernel: str) -> float | None:
    """Roofline time of the compulsory work of every call of ``kernel`` in the
    traced window over the kernel's device time there, in percent."""
    if run.trace is None or run.cell.spec["kernel"] != kernel:
        return None
    t = run.kernel_seconds()
    if t <= 0:
        return None
    nbytes, flops = run.work
    return roofline_s(run.peak, nbytes, flops) * run.domains * run.steps / t * 100.0


class CompileCounter:
    """Counts JAX trace, lowering, compile and compile-cache events."""

    def __init__(self):
        import jax.monitoring as mon

        self.count = 0
        self._mon = mon
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name, *args, **kwargs):
        if name.startswith(("/jax/compilation_cache/", "/jax/core/compile")):
            self.count += 1

    def _duration(self, name, *args, **kwargs):
        self._event(name)

    def close(self):
        self._mon.unregister_event_listener(self._event)
        self._mon.unregister_event_duration_listener(self._duration)


def check_rows(cell: Cell, shape, row_bytes: int) -> int:
    """Rows of z per checked block: the largest divisor of the defined z
    extent whose input slab, at ``row_bytes`` a row, stays under ``SLAB_BYTES``."""
    defined = shape[0] - 2 * cell.spec["halo"]
    return max(d for d in range(1, defined + 1) if defined % d == 0 and (
        d == 1 or (d + 2 * cell.spec["halo"]) * row_bytes <= SLAB_BYTES))


def early_rows(cell: Cell, shape, row_bytes: int) -> int:
    """Rows of z checked in an early sample: as many as keep its input slab
    under ``EARLY_SLAB_BYTES``, at least one."""
    h = cell.spec["halo"]
    return max(1, min(shape[0] - 2 * h, EARLY_SLAB_BYTES // row_bytes - 2 * h))


def make_slab(cell: Cell, rows: int):
    """Jitted: rows [a - halo, a + rows + halo) of every field of a domain;
    z is the third axis from the end."""
    import jax
    from jax import lax

    h = cell.spec["halo"]
    return jax.jit(lambda domain, a: {k: lax.dynamic_slice_in_dim(v, a - h, rows + 2 * h, axis=v.ndim - 3)
                                      for k, v in domain.items()})


def make_check(cell: Cell, rows: int):
    """Jitted: the check of one block of ``rows`` rows against the reference."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    h = cell.spec["halo"]
    outputs = cell.spec["outputs"]

    @jax.jit
    def check(inp, out, a):
        """(max |out - ref|, max |ref|) per output over rows [a, a + rows) and
        the defined y range, the reference run on this block's input rows."""
        with jax.default_matmul_precision("highest"):
            ref = cell.ref.step({k: lax.dynamic_slice_in_dim(v, a - h, rows + 2 * h, axis=v.ndim - 3)
                                 for k, v in inp.items()})
        res = {}
        for k in outputs:
            z = out[k].ndim - 3
            got = lax.dynamic_slice_in_dim(out[k], a, rows, axis=z)
            want = lax.slice_in_dim(ref[k], h, h + rows, axis=z)
            got = lax.slice_in_dim(got, h, got.shape[z + 1] - h, axis=z + 1)
            want = lax.slice_in_dim(want, h, want.shape[z + 1] - h, axis=z + 1)
            res[k] = (jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))),
                      jnp.max(jnp.abs(want.astype(jnp.float32))))
        return res

    return check


def sample_plan(rng: np.random.Generator, cell: Cell, shape, domains: int, rows: int):
    """(domain, early step, first row) of each early sample: one domain from
    each of ``EARLY_SAMPLES`` equal strata of the ensemble."""
    h, nz = cell.spec["halo"], shape[0]
    n = min(EARLY_SAMPLES, domains)
    plan = []
    for s in range(n):
        d = int(rng.integers(s * domains // n, (s + 1) * domains // n))
        plan.append((d, int(rng.integers(0, EARLY_STEPS)), int(rng.integers(h, nz - h - rows + 1))))
    return plan


def run_cell(cell: Cell, seed: int, seconds: float, trace_dir: Path | None, t_start: float,
             *, peak: dict | None, interpret_block=None, fault=None, step_override=None) -> dict:
    """Set up, measure for ``seconds``, check, and return the result fields.

    ``interpret_block`` runs the kernel in interpret mode with that block (no
    chip: the estimator is not asked).  ``fault`` wraps the step of all
    domains, and ``step_override`` replaces one domain's step, to show that
    ``correct`` catches a broken timed path.
    """
    import jax
    from jax import profiler

    import trace_reduce

    cfg = cell.config
    shape, ndom = tuple(cell.traffic["domain"]), int(cell.traffic["domains"])
    run = Run(cell, shape, ndom, peak=peak)
    one_domain = jax.eval_shape(lambda k: cfg.make_domain(k, shape), seed_key(0))
    row_bytes = sum(v.size * v.dtype.itemsize for v in one_domain.values()) // shape[0]
    rows, erows = check_rows(cell, shape, row_bytes), early_rows(cell, shape, row_bytes)
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
    plan = sample_plan(rng, cell, shape, ndom, erows)

    if interpret_block is None:
        one = cfg.step
        if trace_dir is not None:  # the pick the jitted entry point makes, timed
            t = time.perf_counter()
            run.pick = tuple(cfg.select(shape))
            run.select_s = time.perf_counter() - t
    else:
        run.pick = tuple(interpret_block)

        def one(d):
            return cfg.step(d, block=run.pick, interpret=True)
    if step_override is not None:
        one = step_override

    def step_all(ds):
        return [one(d) for d in ds]
    if fault is not None:
        step_all = fault(step_all)

    make = jax.jit(lambda key, i: cfg.make_domain(jax.random.fold_in(key, i), shape))
    slab = make_slab(cell, erows)
    key = seed_key(seed)
    t_fields = time.perf_counter()
    cur = jax.block_until_ready([make(key, i) for i in range(ndom)])
    t_warm = time.perf_counter()
    for _ in range(WARM_STEPS):  # compiles or loads the step for every input it will see
        cur = jax.block_until_ready(step_all(cur))
    jax.block_until_ready(slab(cur[0], plan[0][2]))
    run.setup_s = time.perf_counter() - t_start
    log(f"setup {run.setup_s:.3f} s: to fields {t_fields - t_start:.3f} s, fields "
        f"{t_warm - t_fields:.3f} s, warm-up {time.perf_counter() - t_warm:.3f} s; "
        f"pick {run.pick} (select {run.select_s * 1e3:.2f} ms); {ndom} x {shape}; "
        f"check rows {rows}, early sample rows {erows}")

    samples = []  # (input slab, output slab) of each early sample
    counter = CompileCounter()
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        profiler.start_trace(str(trace_dir / "window"), profiler_options=opts)
    with profiler.TraceAnnotation(WINDOW):
        t0 = time.perf_counter()
        while True:
            with profiler.TraceAnnotation(ENQUEUE):
                new = step_all(cur)
                for d, k, a in plan:
                    if k == run.steps:
                        samples.append((slab(cur[d], a), slab(new[d], a)))
            run.steps += 1
            with profiler.TraceAnnotation(WAIT):
                jax.block_until_ready(cur)
            if run.steps > EARLY_STEPS and time.perf_counter() - t0 >= seconds:
                with profiler.TraceAnnotation(WAIT):
                    jax.block_until_ready(new)
                run.window_s = time.perf_counter() - t0
                break
            cur = new
    if trace_dir is not None:
        profiler.stop_trace()
    counter.close()
    if counter.count:
        raise RuntimeError(f"{counter.count} compile events inside the measured window")
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    log(f"window {run.window_s:.4f} s, {run.steps} steps, "
        f"peak bytes in use {device['memory_peak_bytes']}")

    breakdown = None
    if trace_dir is not None:
        names = {WINDOW, ENQUEUE, WAIT}
        w = trace_reduce.window(trace_reduce.load(_xplane(trace_dir / "window"), names), WINDOW)
        run.trace = w
        device.update(busy_s=w.busy_s, window_s=w.seconds)
        breakdown = {"device_ops": trace_reduce.top_ops(w.op_seconds),
                     "idle_gaps": [[n, s] for n, s, _ in w.idle_gaps]}
        log(f"trace: window {w.seconds:.4f} s, busy {w.busy_s:.4f} s; ops "
            + ", ".join(f"{n} x{w.op_counts[n]} {t:.4f} s" for n, t in breakdown["device_ops"])
            + "; longest gaps " + ", ".join(f"{n} {s:.6f} s at {at:.4f} s" for n, s, at in w.idle_gaps[:3]))
        if interpret_block is None:
            per_call = run.kernel_seconds() / (run.steps * ndom)
            run.ladder = ladder(cell, shape, new[0], trace_dir / "ladder", per_call)

    # the reference runs once the window's state is freed: only the last
    # step's inputs and outputs and the early samples are kept
    last_in, last_out = cur, new
    del cur, new
    checks = compare(cell, make_check(cell, erows), make_check(cell, rows), rows, samples,
                     last_in, last_out, shape)
    del last_in, last_out, samples
    failed = sum(1 for k, (v, lim) in checks.items() if not v <= lim)

    metrics = {}
    for entry, reader in (cell.per_layer if trace_dir is not None else cell.end_to_end):
        value = reader.read(run)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    result = {"correct": failed == 0, "attempted": run.steps * ndom, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return result


def _xplane(log_dir: Path) -> Path:
    (path,) = sorted(log_dir.glob("plugins/profile/*/*.xplane.pb"))
    return path


def compare(cell: Cell, check_early, check, rows: int, samples, last_in, last_out, shape) -> dict:
    """Worst relative error per output, max |out - ref| / max |ref| taken per
    checked domain-step, against its limit: ``check_early`` on the early
    samples' slabs, ``check`` on blocks of ``rows`` rows of the last step."""
    h, nz = cell.spec["halo"], shape[0]
    worst = {k: 0.0 for k in cell.spec["outputs"]}

    def fold(parts):
        for k in worst:
            err = max(float(p[k][0]) for p in parts)
            scale = max(float(p[k][1]) for p in parts)
            rel = err / scale if scale > 0 else err
            if not (math.isnan(worst[k]) or rel <= worst[k]):  # a NaN stays
                worst[k] = rel

    for inp, out in samples:
        fold([check_early(inp, out, h)])
    for inp, out in zip(last_in, last_out):
        fold([check(inp, out, a) for a in range(h, nz - h, rows)])
    return {f"{k}_rel_err": (v, cell.limits[k]) for k, v in worst.items()}


def ladder(cell: Cell, shape, domain, log_dir: Path, per_call: float) -> dict:
    """Kernel device seconds per call of every candidate block the compiler
    accepts, each called on ``domain`` under the profiler: ``LADDER_MIN_CALLS``
    times, or as often as ``LADDER_FLOOR_S`` of kernel time takes at
    ``per_call`` seconds a call, the pick's time in the window."""
    import jax
    from jax import profiler

    import trace_reduce

    cfg = cell.config
    blocks, refused = [], []
    for b in cfg.candidates(shape):
        try:
            jax.block_until_ready(cfg.step(domain, block=b))
            blocks.append(b)
        except Exception as e:  # a candidate the compiler refuses is left out, and counted
            refused.append(b)
            log(f"ladder: block {b} refused: {type(e).__name__}: {str(e)[:300]}")
    calls = max(LADDER_MIN_CALLS, math.ceil(LADDER_FLOOR_S / per_call)) if per_call > 0 else LADDER_MIN_CALLS
    log(f"ladder: {len(blocks) + len(refused)} candidates, {len(refused)} refused {refused}; "
        f"{calls} calls each")
    profiler.start_trace(str(log_dir))
    for b in blocks:
        with profiler.TraceAnnotation(f"block {b}"):
            for _ in range(calls):
                out = cfg.step(domain, block=b)
            jax.block_until_ready(out)
    profiler.stop_trace()
    trace = trace_reduce.load(_xplane(log_dir), {f"block {b}" for b in blocks})
    times = {}
    for b in blocks:
        w = trace_reduce.window(trace, f"block {b}")
        t = trace_reduce.matching(w.op_seconds, cell.spec["kernel_pattern"])
        if t > 0:
            times[tuple(b)] = t / calls
    log("ladder: kernel seconds per call " + ", ".join(f"{b}: {t:.6e}" for b, t in times.items()))
    return times
