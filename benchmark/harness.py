"""One run of one cell: set-up, the measured window, and `correct`.

`run_cell` is what `run.py` calls once it has found the chip; the tests
under `benchmark/tests` call it on the CPU backend at a tiny size, which
`run.py` itself never does.
"""
import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import check                     # noqa: E402
import reference_train           # noqa: E402
import traffic                   # noqa: E402
import weights as _weights       # noqa: E402

FIRST_STEPS = 3                  # the steps the plain reference follows
TRACE_SKIP = 2                   # traced steps before the traced window


def load_file(kind, name):
    """The module `benchmark/<kind>/<name>.py`, found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError("no %s named %r: %s" % (kind, name, path))
    if kind == "reference":      # a package: the references share helpers
        return importlib.import_module("reference." + name)
    spec = importlib.util.spec_from_file_location(
        "benchmark_%s_%s" % (kind, name.replace(".", "_")), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(bench, name):
    """(the cell's file, its configuration's file) for the cell `name`; the
    configuration's path is the one `BENCHMARK.json` gives."""
    cell = load_json("workloads", name + ".json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(os.path.dirname(HERE), conf["file"])) as f:
        return cell, json.load(f)


def process_start():
    """When this process started, on time.time()'s clock."""
    try:
        with open("/proc/self/stat") as f:
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return None


def drive(loop, feed, stop, after_step=None):
    """The loop both set-up and the window run: take a staged batch, run
    the entry, fetch the loss. Returns one record a step:
    (start, got batch, dispatched, fetched, loss), host clock."""
    from jax.profiler import TraceAnnotation
    clock = time.perf_counter
    steps = []
    while True:
        t0 = clock()
        with TraceAnnotation("next_batch"):
            staged = next(feed)
        t1 = clock()
        with TraceAnnotation("step"):
            handle = loop.step(staged)
        t2 = clock()
        with TraceAnnotation("fetch_loss"):
            loss = loop.fetch(handle)
        t3 = clock()
        steps.append((t0, t1, t2, t3, loss))
        if after_step is not None:
            after_step(len(steps))
        if stop(len(steps), t3):
            return steps


def first_steps(loop, feed):
    """Drive the loop through the steps the reference follows and read
    from the program's state what `correct` compares: each step's loss,
    the first gradient with its norms and the batch norms' first batch
    variances (after step 1, kept on the host), and the norms of the
    parameters' change (after the last)."""
    prog = {}

    def after(i):
        if i == 1:
            prog["grad"] = loop.first_gradient()
            prog["grad_norms"] = {k: float(np.linalg.norm(v.ravel()))
                                  for k, v in prog["grad"].items()}
            prog["variances"] = loop.first_variances()
        if i == FIRST_STEPS:
            prog["change_norms"] = loop.change_norms()

    steps = drive(loop, feed, lambda i, _: i >= FIRST_STEPS, after)
    prog["losses"] = [s[4] for s in steps]
    return prog


def _memory_peak(devices):
    """Peak bytes on the fullest chip. The TPU runtime counts live buffers
    (`peak_bytes_in_use`) apart from what it sets aside for the loaded
    programs' temporaries (`peak_bytes_reserved`); a chip's memory holds
    both."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def reference_readings(config, cell, seed, pool, devices, mode="float32",
                       rows=None, unchanged=False):
    """The plain reference's first steps from the same seed and batches."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    ref = load_file("reference", config["reference"])
    shapes = {k: tuple(v) for k, v in cell["_shapes"].items()}
    w = _weights.make_weights(shapes, config["initializer"], seed, devices[0])
    batches = pool[:FIRST_STEPS]
    if len(devices) > 1:
        # the single-program reference at the global batch, its rows laid
        # over the chips only so that float32 activations fit
        mesh = Mesh(list(devices), ("rows",))
        by_rows = NamedSharding(mesh, PartitionSpec("rows"))
        everywhere = NamedSharding(mesh, PartitionSpec())
        w = jax.device_put(w, everywhere)
        if rows is None:
            batches = [(jax.device_put(x, by_rows), jax.device_put(y, by_rows))
                       for x, y in batches]
    return reference_train.follow(
        ref, w, batches, config["optimizer"], mode,
        config.get("reference_kwargs"), rows, unchanged)


def run_cell(cell, config, bench, seed, seconds, trace, devices, peaks,
             started=None, log=sys.stderr, load_trace=None):
    """Returns the result object of one run (the last line's JSON)."""
    import jax
    from mxnet_tpu.compile import cache

    started = started if started is not None else time.time()
    say = lambda *a: print(*a, file=log, flush=True)     # noqa: E731
    since = lambda: time.time() - started                # noqa: E731
    say("compile cache: %s (%.1f s since the process started)"
        % (cache.enable_cache(), since()))
    dev0 = devices[0]
    pool = traffic.make_pool(cell, config, seed)
    say("pool of %d host batches made (%.1f s)" % (len(pool), since()))
    loop = load_file("loops", cell["loop"]).Loop(cell, config, seed, devices)
    say("model, weights and trainer built (%.1f s)" % since())
    cell["_shapes"] = {k: tuple(v.shape) for k, v in loop.weights.items()}
    feed = iter(loop.feed(traffic.cycle(pool)))

    # -- set-up: the first steps, through the window's own call and feed --
    t_first = time.perf_counter()
    prog = first_steps(loop, feed)
    say("first steps: losses %s, %.2f s (compiles or loads the programs)"
        % (" ".join("%.6f" % v for v in prog["losses"]),
           time.perf_counter() - t_first))
    drive(loop, feed, lambda i, _: i >= int(cell.get("warmup_steps", 5)))

    # -- the window -------------------------------------------------------
    from mxnet_tpu.observability import registry as _obs
    before = cache.cache_stats()
    wait0 = _batch_wait(_obs)
    length = float(seconds)
    setup_s = time.time() - started
    t_open = time.perf_counter()
    steps = drive(loop, feed, lambda _, now: now - t_open >= length)
    after = cache.cache_stats()
    wait1 = _batch_wait(_obs)
    if wait0 is not None and wait1 is not None:
        say("the program's own io.batch_wait.seconds: %.3f ms a step"
            % (1e3 * (wait1 - wait0) / len(steps)))
    say("window: %d steps in %.2f s, loss %.4f at its first step, %.4f at "
        "its last; slowest steps (index: ms) %s"
        % (len(steps), steps[-1][3] - t_open, steps[0][4], steps[-1][4],
           " ".join("%d: %.0f" % (i, 1e3 * (s[3] - s[0])) for i, s in sorted(
               enumerate(steps), key=lambda p: p[1][0] - p[1][3])[:3])))

    # -- the traced tail: the same loop, some steps under the profiler ----
    trace_dir = os.path.join(os.path.dirname(HERE), ".bench_trace",
                             cell["name"])
    traced = 0
    if trace:
        # counted in steps and short: a trace of 12 ResNet steps is 150 MB.
        # The host tracer is off: its events stall the steps (PERF.md), and
        # `drive` keeps the host's side of each step on its own clock
        traced = int(cell.get("trace_steps", 10))
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 0
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        opts.advanced_configuration = {"tpu_trace_mode": "TRACE_ONLY_XLA"}
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        # the profiler's own start-up can stall the first steps under it
        tail = drive(loop, feed, lambda i, _: i >= TRACE_SKIP + traced)
        jax.profiler.stop_trace()
        say("traced steps, ms: %s" % " ".join(
            "%.0f" % (1e3 * (s[3] - s[0])) for s in tail))
    memory_peak = _memory_peak(devices)
    loop.close()
    del loop, feed
    gc.collect()

    run = {
        "cell": cell, "config": config, "chips": len(devices),
        "steps": steps, "t_open": t_open, "setup_s": setup_s,
        "batch": int(cell["batch"]),
        "compiles": (after["hits"] + after["misses"]
                     - before["hits"] - before["misses"]),
        "memory_peak_bytes": memory_peak,
        "peak": peaks, "trace": None, "traced_steps": traced,
        "flops_per_step": load_file("flops", config["flops"])
        .train_flops_per_sample(config) * int(cell["batch"]),
    }
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": False, "attempted": len(steps),
              "failed": sum(1 for s in steps if not math.isfinite(s[4]))}
    breakdown = None
    if trace:
        import trace_reduce
        tr = (load_trace or trace_reduce.load)(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if not tr.host_spans:
            tr.take_host_steps([s[:4] for s in tail])
        window = tr.window(skip=TRACE_SKIP)
        if not tr.devices or window is None:
            raise RuntimeError("the trace holds no device plane: no "
                               "operation ran on a chip")
        run["trace"], run["trace_window"] = tr, window
        busy = [d.busy_seconds(window) for d in tr.devices]
        device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = window[1] - window[0]
        worst = tr.devices[busy.index(max(busy))]
        ops = sorted(worst.op_seconds(window).items(), key=lambda kv: -kv[1])
        breakdown = {"device_ops": [[k, v] for k, v in ops[:10]],
                     "idle_gaps": tr.name_gaps(
                         tr.devices[busy.index(min(busy))], window, top=5)}

    # -- the metrics, each by the reader its name finds ------------------
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for entry in bench[kind]:
        if "workloads" in entry and cell["name"] not in entry["workloads"]:
            continue
        value = load_file("metrics", entry["name"]).read(run)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown

    # -- correct: the plain reference, once the program's state is freed --
    t_ref = time.perf_counter()
    ref = reference_readings(config, cell, seed, pool, devices)
    numbers, where = check.readings(prog, ref, cell["_shapes"])
    ok, rows = check.verdict(numbers, cell["limits"])
    ok = ok and result["failed"] == 0 and len(steps) > 0
    result["correct"] = bool(ok)
    say("reference: losses %s, %.2f s" % (
        " ".join("%.6f" % v for v in ref["losses"]),
        time.perf_counter() - t_ref))
    result["compared"] = {n: {"value": v, "limit": lim} for n, v, lim in rows}
    result["compared"]["worst_leaf"] = where
    result["compared"]["unheld"] = {k: v for k, v in numbers.items()
                                    if k not in cell["limits"]}
    for name, value, limit in rows:
        say("compared %s %r limit %r" % (name, value, limit))
    return result


def _batch_wait(obs):
    """Seconds the consumer has blocked in the program's prefetcher, by
    the program's own histogram (`io.batch_wait.seconds`); None where the
    loop does not go through it."""
    hist = obs.REGISTRY.get("io.batch_wait.seconds")
    return None if hist is None else float(hist.total_sum())
