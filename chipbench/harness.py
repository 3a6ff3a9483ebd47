"""The loop a user of the library runs, its timers, and the last line.

One process holds the cell's chips and does what Horovod's users do:
``hvd.init()``, ``create_train_state``, ``distributed(<optax optimizer>)``,
``make_train_step`` with its defaults (donated state, one dispatch a step),
batches through ``Dataset`` -> ``Prefetcher`` -> ``shard_batch``. The loop
fetches the loss of step i after step i+1 has been dispatched (run-ahead of
one, as a loop that logs the previous step's loss does) and notes the host
time at which each loss became ready.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is in a file of its own, found by name (PERF.md section 4
says how to add one).
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import math
import os
import sys
import tempfile
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
END_TO_END = {"samples_per_s_chip": "samples/s/chip", "step_ms_p95": "ms",
              "setup_s": "s"}
WARM_STEPS = 6          # steady steps after the two blocking calls
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def info(**line):
    """An earlier line of standard output: facts the last line has no key
    for. The last line is the result."""
    print(json.dumps({"info": line.pop("what"), **line}), flush=True)


# ------------------------------------------------------------- finding files

def _json(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def resolve(workload):
    """``(cell, config, traffic)`` of a cell named in BENCHMARK.json."""
    manifest = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"chipbench: no workload {workload!r} in "
                         f"BENCHMARK.json (has {sorted(cells)})")
    cell = cells[workload]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = _json(os.path.join(ROOT, entry["file"]))
    traffic = _json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    return cell, config, traffic


def layer_metrics():
    """Every reader under ``layer_metrics/``, by metric name."""
    found = {}
    folder = os.path.join(HERE, "layer_metrics")
    for fname in sorted(os.listdir(folder)):
        if fname.endswith(".py") and not fname.startswith("_"):
            spec = importlib.util.spec_from_file_location(
                "chipbench_layer_metric_" + fname[:-3].replace(".", "_"),
                os.path.join(folder, fname))
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            found[fname[:-3]] = module
    return found


# ------------------------------------------------------------------ the chip

def require_chips(chips):
    """The measurement path's only door: a TPU whose kind has peaks on
    record, and exactly the chips the cell asks for."""
    import jax
    from . import peaks
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU; jax found platform "
                         f"{d.platform!r} ({d.device_kind!r} x {len(devices)})")
    table = peaks.for_kind(d.device_kind)
    if len(devices) != chips:
        raise SystemExit(f"chipbench: the cell asks for {chips} chip(s); "
                         f"jax found {len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}, table


def memory_peak_bytes():
    """Peak on the fullest chip: live arrays plus the running program's
    scratch, which this runtime books under "reserved" (PERF.md section 7)."""
    import jax
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, stats.get("peak_bytes_in_use", 0)
                   + stats.get("peak_bytes_reserved", 0))
    return peak


class CompileCounter:
    """Counts programs handed to the backend's compiler (or looked up in the
    persistent cache, which only a new trace does), from JAX's monitoring."""

    def __init__(self):
        import jax.monitoring
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.n += 1


def named_leaves(tree):
    """A pytree as ``{"a/b/c": host array}``: what a reference reads by name.
    flax's partitioning boxes (attribute ``value``) are looked through."""
    import jax
    import numpy as np
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [str(getattr(k, "key", getattr(k, "name", k))) for k in path]
        out["/".join(k for k in keys if k != "value")] = np.asarray(leaf)
    return out


def reference_losses(reference, config, start, batches):
    """The plain reference's losses on ``batches``, one update after each,
    from the parameters ``start`` (``named_leaves`` of the system's)."""
    import jax
    params = {k[len("params/"):]: v for k, v in start.items()
              if k.startswith("params/")}
    init, step = reference.program(config, jax.devices())
    state, losses = init(params), []
    with jax.default_matmul_precision("highest"):
        for batch in batches:
            params, state, loss = step(params, state, batch)
            losses.append(float(loss))
    return losses


# ------------------------------------------------------------------ the loop

class Record:
    def __init__(self):
        self.waits, self.dispatches, self.done, self.losses = [], [], [], []
        self.pending = None


def run_steps(step, state, feed, rec, until):
    """Run-ahead-of-one steps until ``until(rec)`` after a completion."""
    from jax.profiler import TraceAnnotation
    while True:
        t0 = time.perf_counter()
        with TraceAnnotation("chipbench:next(feed)"):
            batch = next(feed)
        t1 = time.perf_counter()
        with TraceAnnotation("chipbench:step call"):
            state, loss = step(state, *batch)
        t2 = time.perf_counter()
        rec.waits.append(t1 - t0)
        rec.dispatches.append(t2 - t1)
        if rec.pending is not None:
            with TraceAnnotation("chipbench:loss fetch"):
                value = float(rec.pending)
            rec.done.append(time.perf_counter())
            rec.losses.append(value)
        rec.pending = loss
        if rec.done and until(rec):
            return state


def run_cell(cell, config, traffic, *, seed, seconds, traced, device, peaks,
             t_process):
    """One run of one cell. Returns the last line as a dict."""
    import jax
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.collectives import Compression
    from horovod_tpu.data import Dataset, Prefetcher
    from horovod_tpu.optimizer import distributed
    from horovod_tpu.train import create_train_state, make_train_step

    family = importlib.import_module("chipbench.families." + config["family"])
    reference = importlib.import_module(
        "chipbench.reference." + config["family"])
    compiles = CompileCounter()
    hvd.init()
    chips = hvd.size()
    global_batch = traffic["global_batch"]
    task = family.build(config, traffic)

    t = time.perf_counter()
    pool = task.pool(seed)
    pool_s = time.perf_counter() - t
    dataset = Dataset(pool, batch_size=global_batch, shuffle=True, seed=seed)
    first_batches = []

    def host_batches():
        """Epoch after epoch of the shuffled pool. The very first batch is
        fed twice, so that the second loss is the first update's effect on
        the data it was computed from: what the reference check leans on."""
        epoch = 0
        while True:
            dataset.set_epoch(epoch)
            for batch in dataset:
                if not first_batches:
                    first_batches.extend([batch, batch])
                    yield batch
                yield batch
            epoch += 1

    exchange = config["exchange"]
    dopt = distributed(task.optimizer, compression=getattr(
        Compression, exchange["compression"]))
    t = time.perf_counter()
    state = create_train_state(task.model, jax.random.PRNGKey(seed),
                               pool[0][:1], dopt)
    jax.block_until_ready(state)
    state_init_s = time.perf_counter() - t
    # The reference starts from these; the step donates the device's copy.
    start = named_leaves({"params": state.params})
    step = make_train_step(task.model, dopt, task.loss_fn)

    rec = Record()
    with Prefetcher(host_batches()) as feed:
        blocking = []
        for _ in range(2):          # first call compiles or loads the cache
            batch = next(feed)
            t = time.perf_counter()
            state, loss = step(state, *batch)
            rec.losses.append(float(loss))
            blocking.append(time.perf_counter() - t)
        first_step_s, second_step_s = blocking
        state = run_steps(step, state, feed, rec,
                          lambda r: len(r.done) >= WARM_STEPS)
        i0, t_start, c0 = len(rec.done), rec.done[-1], compiles.n
        state = run_steps(step, state, feed, rec,
                          lambda r: r.done[-1] - t_start >= seconds)
        i1, compiled_in_window = len(rec.done), compiles.n - c0
        setup_s = t_start - t_process
        peak_bytes = memory_peak_bytes()

        reduced = None
        if traced:
            from . import trace as _trace
            with tempfile.TemporaryDirectory(prefix="chipbench-") as logdir:
                # Host tracing at level 1 (the loop's annotations) and no
                # Python call tracing: at the default level the runtime's
                # host-to-device linearisation threads write an event per
                # chunk, which slowed ResNet-50's 77 MB batches until the
                # device starved (80% idle while traced, a 280 MB trace).
                options = jax.profiler.ProfileOptions()
                options.host_tracer_level = 1
                options.python_tracer_level = 0
                jax.profiler.start_trace(logdir, profiler_options=options)
                try:
                    n_trace = max(4, traffic.get("trace_steps", 20))
                    state = run_steps(step, state, feed, rec,
                                      lambda r: len(r.done) >= i1 + n_trace)
                    rec.losses.append(float(rec.pending))
                    rec.pending = None
                finally:
                    jax.profiler.stop_trace()
                paths = [os.path.join(d, f) for d, _, fs in os.walk(logdir)
                         for f in fs if f.endswith(".xplane.pb")]
                info(what="trace", files=len(paths),
                     bytes=sum(os.path.getsize(p) for p in paths))
                t = time.perf_counter()
                reduced = _trace.reduce(_trace.load(paths[0]))
                info(what="trace_reduced", steps=reduced.steps,
                     window_s=reduced.window_s, busy_s=reduced.busy_s,
                     device_planes=reduced.n_device_planes,
                     reduce_s=time.perf_counter() - t)
        if rec.pending is not None:
            rec.losses.append(float(rec.pending))
    memory_stats = [d.memory_stats() for d in jax.devices()]
    del state, loss, batch, feed

    # ---- the window's numbers
    done = rec.done[i0:i1]
    intervals = np.diff([t_start] + done)
    n = len(done)
    window_losses = rec.losses[2 + i0:2 + i1]
    failed = sum(not math.isfinite(v) for v in window_losses)
    end_to_end = {
        "samples_per_s_chip": n * global_batch / (done[-1] - t_start) / chips,
        "step_ms_p95": float(np.percentile(intervals, 95)) * 1e3,
        "setup_s": setup_s,
    }
    info(what="window", steps=n, window_s=done[-1] - t_start,
         interval_samples=len(intervals),
         interval_ms_median=float(np.median(intervals)) * 1e3,
         interval_ms_max=float(intervals.max()) * 1e3,
         compiled_in_window=compiled_in_window, global_batch=global_batch,
         chips=chips, pool_s=pool_s, state_init_s=state_init_s,
         first_step_s=first_step_s, second_step_s=second_step_s,
         memory_stats=memory_stats)

    # ---- the reference check, after the system's state is gone
    t = time.perf_counter()
    ref_losses = reference_losses(reference, config, start, first_batches)
    reference_s = time.perf_counter() - t
    tol = config["tolerance"]["loss_rel"]
    problems = [f"step {i}: system loss {s!r} vs reference {r!r}"
                for i, (s, r) in enumerate(zip(rec.losses[:2], ref_losses))
                if not abs(s - r) <= tol * abs(r)]
    if compiled_in_window:
        problems.append(f"{compiled_in_window} compilation(s) inside the "
                        "measured window")
    info(what="reference", system=rec.losses[:2], reference=ref_losses,
         loss_rel_tolerance=tol, reference_s=reference_s, problems=problems)

    # ---- the last line
    metrics = {k: {"value": v, "unit": END_TO_END[k]}
               for k, v in end_to_end.items()}
    device = dict(device, memory_peak_bytes=peak_bytes)
    line = {"correct": not problems, "attempted": n, "failed": failed,
            "metrics": metrics, "device": device}
    expected = set(END_TO_END)
    if traced:
        run = types.SimpleNamespace(
            cell=dict(cell, config=config, traffic=traffic), task=task,
            peaks=peaks, trace=reduced, waits=rec.waits[i0:i1],
            dispatches=rec.dispatches[i0:i1],
            state_init_s=state_init_s, first_step_s=first_step_s, info=info)
        for name, reader in layer_metrics().items():
            if not reader.applies(run.cell):
                continue
            expected.add(name)
            value = reader.read(run)
            if value is not None:
                metrics[name] = {"value": value, "unit": reader.UNIT}
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        line["breakdown"] = {"device_ops": reduced.top_ops(10),
                             "idle_gaps": reduced.idle_gaps(10)}
    faults = validate_line(line, expected, traced)
    if faults:
        raise SystemExit("chipbench: refusing to print a malformed result: "
                         + "; ".join(faults))
    return line


# ------------------------------------------------------------- the last line

def _number(x):
    return isinstance(x, (int, float)) and not isinstance(x, bool) \
        and math.isfinite(x)


def validate_line(line, expected_metrics, traced):
    """Every way the last line can be unreadable to the driver, as a list of
    faults (empty when the line is good)."""
    faults = []
    if not isinstance(line, dict):
        return ["the line is not a JSON object"]
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        if key not in line:
            faults.append(f"key {key!r} is missing")
    if faults:
        return faults
    if not isinstance(line["correct"], bool):
        faults.append("'correct' is not true or false")
    for key in ("attempted", "failed"):
        if not isinstance(line[key], int) or isinstance(line[key], bool) \
                or line[key] < 0:
            faults.append(f"{key!r} is not a count")
    metrics = line["metrics"]
    if not isinstance(metrics, dict):
        faults.append("'metrics' is not an object")
        metrics = {}
    for name in sorted(expected_metrics):
        if name not in metrics:
            faults.append(f"metric {name!r} of this cell is missing")
    for name, m in metrics.items():
        if not (isinstance(m, dict) and _number(m.get("value"))
                and isinstance(m.get("unit"), str) and m["unit"]):
            faults.append(f"metric {name!r} is not "
                          '{"value": <number>, "unit": <text>}')
    device = line["device"]
    if not isinstance(device, dict):
        return faults + ["'device' is not an object"]
    for key, kind in (("platform", str), ("kind", str), ("count", int),
                      ("memory_peak_bytes", int)):
        if not isinstance(device.get(key), kind) \
                or isinstance(device.get(key), bool):
            faults.append(f"device.{key} is missing or not {kind.__name__}")
    if traced:
        busy, window = device.get("busy_s"), device.get("window_s")
        if not (_number(busy) and _number(window)):
            faults.append("a traced line needs device.busy_s and "
                          "device.window_s")
        elif not 0 < busy <= window:
            faults.append(f"need 0 < busy_s <= window_s, got {busy} and "
                          f"{window}")
        for key, rows in (line.get("breakdown") or {}).items():
            if len(rows) > 10 or not all(
                    isinstance(r, list) and len(r) == 2
                    and isinstance(r[0], str) and _number(r[1])
                    for r in rows):
                faults.append(f"breakdown.{key} is not at most 10 "
                              "[name, seconds] pairs")
    try:
        json.dumps(line, allow_nan=False)
    except (TypeError, ValueError) as e:
        faults.append(f"the line does not serialise: {e}")
    return faults


def print_line(line):
    sys.stdout.flush()
    print(json.dumps(line, allow_nan=False), flush=True)
