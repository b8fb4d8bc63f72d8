"""One run of one cell: set-up, measured window, trace, comparison, result.

Set-up (``setup_s``) runs from process start through three rounds: the
first compiles (or loads from the persistent cache) and runs the one chunk
shape the cell uses; after round 1 the comparison's readings of the server's
first update are taken and after round 3 those of the parameters' change,
before round 4 takes the state.
The window then runs one round per call until ``--seconds`` have passed;
``round_s`` is its length over the rounds it completed.  With ``--trace 1``
the same window runs under the profiler and the per-layer metrics are read
from the trace instead.  Once the window has closed and the peak memory is
read, the program's state is freed and the reference replays the first
three rounds; ``correct`` is the comparison of the two.
"""

from __future__ import annotations

import gc
import glob
import importlib
import json
import math
import os
import pathlib
import shutil
import sys
import time
import types

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
OUT = BENCH / "out"
SETUP_ROUNDS = 3


class NoChip(RuntimeError):
    pass


class _WindowClosed(Exception):
    pass


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the cell, from BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(workload: str, root: pathlib.Path = ROOT):
    bm = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    within = lambda m: workload in m.get("workloads", [workload])
    return types.SimpleNamespace(
        name=workload, chips=cell["chips"],
        config=_json(root / conf["file"]),
        traffic=_json(root / "bench" / "traffic" / f"{cell['traffic']}.json"),
        job=_json(root / "bench" / "workloads" / f"{workload}.json"),
        end_to_end=[m for m in bm["end_to_end"] if within(m)],
        per_layer=[m for m in bm["per_layer"] if within(m)])


def metric_reader(name: str):
    return importlib.import_module(f"bench.metrics.{name}")


# ---------------------------------------------------------------------------
# devices, cache and compile counters
# ---------------------------------------------------------------------------

def require_chips(chips: int):
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:           # no backend at all
        raise NoChip(str(e)) from None
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips, found {len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Backend compiles and persistent-cache hits/misses, from JAX's events."""

    def __init__(self):
        import jax.monitoring as mon
        self.n = {"compiles": 0, "cache_hits": 0, "cache_misses": 0,
                  "compile_s": 0.0}

        def on_event(name, **_):
            if name.endswith("/cache_hits"):
                self.n["cache_hits"] += 1
            elif name.endswith("/cache_misses"):
                self.n["cache_misses"] += 1

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.n["compiles"] += 1
                self.n["compile_s"] += secs

        mon.register_event_listener(on_event)
        mon.register_event_duration_secs_listener(on_duration)

    def snap(self) -> dict:
        return dict(self.n)


def enable_cache() -> str:
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    d = enable_compile_cache()
    os.makedirs(d, exist_ok=True)       # JAX writes no entry into a missing dir
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def drive(prog, seconds: float, trace_dir=None) -> dict:
    """Set-up rounds, then the window; returns readings and times."""
    import jax
    r = {"loss": []}

    def on_chunk(t, params, state, hist):
        now = time.perf_counter()
        r["loss"] += [float(x) for x in hist["loss"]]
        if t == 1:
            r["grad_norms"], r["grad_dots"] = prog.grad_readings(state)
            r["first_chunk_end"] = now
        if t == SETUP_ROUNDS:
            r["change_norms"], r["change_dots"] = prog.change_readings(params)
            r["setup_end"] = time.perf_counter()
            r["window_round0"] = t
            r["compiles_at_window"] = COUNTER.snap()
            if trace_dir is not None:
                jax.profiler.start_trace(trace_dir,
                                         profiler_options=profile_options())
                r["annotation"] = jax.profiler.TraceAnnotation(
                    "bench_window")
                r["annotation"].__enter__()
            r["window_start"] = time.perf_counter()
        elif t > SETUP_ROUNDS and now - r["window_start"] >= seconds:
            if trace_dir is not None:
                r["annotation"].__exit__(None, None, None)
                jax.profiler.stop_trace()
            r["window_end"] = now
            r["window_rounds"] = t - r["window_round0"]
            r["compiles_after_window"] = COUNTER.snap()
            raise _WindowClosed

    try:
        prog.run(on_chunk)
    except _WindowClosed:
        pass
    return r


COUNTER = None


def profile_options():
    """Device ops and the runtime's host spans; no Python call tracing."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def run_cell(spec, seed: int, seconds: float, trace: bool, t_start: float,
             *, check_chips: bool = True, out_dir: pathlib.Path = OUT) -> dict:
    """The result dict of one run (the contract's last line)."""
    global COUNTER
    import jax
    from bench import compare, program

    devs = require_chips(spec.chips) if check_chips else \
        jax.devices()[:spec.chips]
    COUNTER = COUNTER or CompileCounter()
    t_import = time.perf_counter()
    log(f"jax {jax.__version__}, {devs[0].device_kind!r} x{len(devs)}, "
        f"compile cache {jax.config.jax_compilation_cache_dir}")

    prog = program.build(spec, seed)
    jax.block_until_ready((prog.params, prog.state))
    t_init = time.perf_counter()
    c0 = COUNTER.snap()

    run_dir = out_dir / f"{spec.name}.{seed}"
    trace_dir = None
    if trace:
        shutil.rmtree(run_dir, ignore_errors=True)
        trace_dir = str(run_dir / "window")
    r = drive(prog, seconds, trace_dir)
    setup = {"import_s": t_import - t_start, "init_s": t_init - t_import,
             "first_round_s": r["first_chunk_end"] - t_init,
             "rounds_2_3_s": r["setup_end"] - r["first_chunk_end"],
             "setup_s": r["setup_end"] - t_start}
    cw0, cw1 = r["compiles_at_window"], r["compiles_after_window"]
    log(f"set-up split: {json.dumps(setup)}")
    log(f"compiles in set-up: {cw0['compiles'] - c0['compiles']} "
        f"({cw0['compile_s'] - c0['compile_s']:.2f} s); cache hits "
        f"{cw0['cache_hits']}, misses {cw0['cache_misses']}")
    log(f"compiles inside the window: {cw1['compiles'] - cw0['compiles']}")
    window_s = r["window_end"] - r["window_start"]
    rounds = r["window_rounds"]
    losses = r["loss"]
    log(f"window: {rounds} rounds in {window_s:.4f} s; losses of the first "
        f"rounds {losses[:SETUP_ROUNDS]}")
    dev = device_info(devs)
    failed = sum(1 for x in losses[SETUP_ROUNDS:] if not math.isfinite(x))

    result = {"correct": False, "attempted": rounds, "failed": failed}
    if trace:
        per_layer, extra = _read_trace(spec, prog, trace_dir, rounds, dev,
                                       devs, run_dir)
        result["metrics"] = per_layer
        dev.update(busy_s=extra["busy_s"], window_s=extra["window_s"])
        result["device"] = dev
        result["breakdown"] = extra["breakdown"]
    else:
        result["metrics"] = {
            "round_s": {"value": window_s / rounds, "unit": "s/round"},
            "setup_s": {"value": setup["setup_s"], "unit": "s"}}
        result["device"] = dev
    log(f"memory: peak_bytes_in_use {dev['memory_peak_bytes']}")

    prog_readings = readings_of(r)
    del prog, r
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_readings(spec, seed)
    log(f"reference: {time.perf_counter() - t_ref:.2f} s "
        f"({json.dumps(ref.pop('seconds'))})")
    nums = compare.numbers(prog_readings, ref)
    ok, checks = compare.judge(nums, spec.job["limits"])
    result["correct"] = bool(ok and failed == 0)
    result["checks"] = checks
    return result


def readings_of(r: dict) -> dict:
    """What the comparison reads of a driven program."""
    keys = ("grad_norms", "grad_dots", "change_norms", "change_dots")
    return dict({k: r[k] for k in keys}, loss=r["loss"][:SETUP_ROUNDS])


def reference_readings(spec, seed: int, **faults) -> dict:
    import jax
    from bench import reference, traffic, weights
    cfg = spec.config
    sampler = traffic.ZipfClients.from_traffic(
        spec.traffic, cfg["vocab_size"], weights.stream(seed, "data"))
    state = sampler.init_state()
    sample = jax.jit(sampler.sample)
    batches = [sample(state, t)[1]["tokens"] for t in range(SETUP_ROUNDS)]
    rkey = weights.stream(seed, "rounds")
    keys = [jax.random.fold_in(rkey, t) for t in range(SETUP_ROUNDS)]
    init = weights.make_init(reference.param_shapes(cfg),
                             reference.dtype_of(cfg["dtype"]),
                             cfg["num_layers"])
    return reference.run(cfg, spec.job, init(weights.stream(seed, "weights")),
                         batches, keys, weights.stream(seed, "probe"),
                         **faults)


def _read_trace(spec, prog, trace_dir, rounds, dev, devs, run_dir):
    from bench import counts, devtrace, peaks, stages
    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    fused = devtrace.reduce(devtrace.load(files[-1]))
    stage_s = None
    if any(m["name"] in stages.METRICS for m in spec.per_layer):
        stage_s = stages.measure(spec, prog, str(run_dir / "stages"))
    ctx = types.SimpleNamespace(
        fused=fused, stages=stage_s, rounds=rounds, chips=len(devs),
        peak=peaks.peak(devs[0].device_kind),
        flops_per_round=counts.flops_per_round(spec.config, spec.traffic),
        sketch_bytes=counts.sketch_bytes(spec.traffic["clients"],
                                         prog.plan.d_total,
                                         prog.plan.b_total))
    out = {}
    for m in spec.per_layer:
        v = metric_reader(m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    log(f"trace: {json.dumps(fused)}")
    if stage_s:
        log(f"stages (device s): {json.dumps(stage_s)}")
    return out, {"busy_s": fused["busy_s"], "window_s": fused["window_s"],
                 "breakdown": {"device_ops": fused["device_ops"],
                               "idle_gaps": fused["idle_gaps"]}}


def print_result(result: dict) -> None:
    for k, c in result.get("checks", {}).items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv, t_start: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Run one cell of BENCHMARK.json")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_spec(args.workload)
    try:
        require_chips(spec.chips)
        enable_cache()
        result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                          t_start)
    except NoChip as e:
        log(str(e))
        return 2
    print_result(result)
    return 0
