"""One run of one benchmark cell, driven by data.

Everything a cell is made of is found by name: ``BENCHMARK.json`` names the
cell's configuration and its metrics, ``configs/<config>.json`` holds the
model, its data and the names of its plain reference
(``reference/<name>.py``) and its operation count (``flops/<name>.py``),
``workloads/<cell>.json`` the job, the limits of the correctness check and
the name of its traffic maker (``traffic/<maker>.py``), and
``metrics/<metric>.py`` reads one per-layer metric from the run's facts.  A
new cell, metric, model or kind of traffic is new files.

A run, in one process, the only one to touch the chip:

  1. set-up: make the cell's data from the seed, build the model and a
     ``TrainSession`` as ``launch/train.py`` builds them, train the first
     chunk of rounds (it compiles every program the window runs, and it is
     what the check compares), keep its losses and state on the host, and
     train one chunk more, which warms what only a second call meets;
  2. window: one ``session.train(R)`` call, ``R`` a multiple of the chunk:
     ``--seconds`` times the workload's ``rounds_per_s`` (its rate on the
     TPU v5e when the cell was added), so every run does the same work;
     compilations inside it are counted.  With ``--trace 1`` the same call
     is made untraced (the per-layer counters and rates read it), then a
     short call of ``TRACE_SECONDS`` worth of rounds under the profiler,
     whose device time per round and idle gaps the trace gives;
  3. after the window: the peak of device memory, then the session is
     freed and the plain reference (``bench/reference``) follows the first
     chunk; ``bench/check.py`` decides ``correct``.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import importlib.util
import inspect
import json
import math
import os
import re
import resource
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import check, peaks, trace

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
WINDOW = "bench.window"
#: the traced call's length on the cell's chip.  The profile takes some
#: 2.5 GiB of host memory per second it records (a 13 s one, 32 GiB of the
#: chip host's 40), and tracing slows the STL-10 cell's rounds 3x
TRACE_SECONDS = 2.0
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


# ------------------------------------------------------------------ data files
def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(bench_dir: str = BENCH_DIR) -> dict:
    return read_json(os.path.join(os.path.dirname(bench_dir),
                                  "BENCHMARK.json"))


def workload(name: str, bench_dir: str = BENCH_DIR) -> dict:
    return read_json(os.path.join(bench_dir, "workloads", name + ".json"))


def config(name: str, bench_dir: str = BENCH_DIR) -> dict:
    return read_json(os.path.join(bench_dir, "configs", name + ".json"))


_LOADED: Dict[str, object] = {}


def bench_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """The module ``<kind>/<name>.py`` of the benchmark (names may hold
    dots): a per-layer metric's reader, a traffic maker, a plain reference
    or an operation count.  Each file is loaded once per process."""
    path = os.path.join(bench_dir, kind, name + ".py")
    if path not in _LOADED:
        # registered before it runs, as a dataclass in it needs
        key = f"bench_{kind}_{re.sub(r'\W', '_', name)}_{len(_LOADED)}"
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]


def cell_parts(cfg: dict, work: dict, bench_dir: str = BENCH_DIR):
    """(traffic maker, plain reference, operation count) of a cell."""
    return (bench_module("traffic", work["maker"], bench_dir),
            bench_module("reference", cfg["reference"], bench_dir),
            bench_module("flops", cfg["flops"], bench_dir))


def cell_metrics(spec: dict, cell: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that ``cell`` reports."""
    return [m for m in spec[kind]
            if "workloads" not in m or cell in m["workloads"]]


# ------------------------------------------------------------------- compiles
class CompileCounter:
    """Counts lowerings and backend compiles while armed."""

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if self.armed and event in COMPILE_EVENTS:
            self.count += 1


# --------------------------------------------------------------- the program
def program_object(path: str):
    """``package.module.name`` of the program."""
    mod_name, name = path.rsplit(".", 1)
    return getattr(importlib.import_module(mod_name), name)


def program_config(cfg: dict):
    """The program's model configuration, from the constructor the config
    file names.  Every size of the file's ``model`` that the configuration
    object has (an attribute, or a method it calls without arguments) must
    be the file's own."""
    pcfg = program_object(cfg["program"]["builder"])(
        **cfg["program"].get("args", {}))
    got, want = {}, {}
    for key, value in cfg["model"].items():
        if not hasattr(pcfg, key):
            continue
        have = getattr(pcfg, key)
        have = have() if inspect.ismethod(have) else have
        got[key] = (np.dtype(have).name if key == "dtype" else
                    list(have) if isinstance(have, tuple) else have)
        want[key] = value
    if not got or got != want:
        raise RuntimeError(f"{cfg['name']}: the program builds {got}, the "
                           f"configuration file states {want}")
    return pcfg


def build_session(cfg: dict, job: dict, seed: int, shards):
    """A ``TrainSession`` built as ``launch/train.py`` builds one, on the
    model class the configuration file names."""
    from repro.api import TrainSession
    from repro.config import HeteroProfile, OptimizerConfig, SplitEEConfig

    model = program_object(cfg["program"]["model"])(program_config(cfg),
                                                    seed=seed)
    opt = job["opt"]
    splitee = SplitEEConfig(profile=HeteroProfile(tuple(job["splits"])),
                            strategy=job["strategy"],
                            aggregate_every=job["aggregate_every"])
    opt_cfg = OptimizerConfig(lr=opt["lr"], min_lr=opt["min_lr"],
                              total_steps=opt["total_steps"], warmup_steps=0,
                              b1=opt["b1"], b2=opt["b2"], eps=opt["eps"])
    session = TrainSession.from_config(
        model, splitee, opt_cfg, shards, batch_size=job["batch"],
        engine=job["engine"], seed=seed, grad_mode=job["grad_mode"])
    if session.engine.name != job["expect_engine"]:
        raise RuntimeError(f"engine {session.engine_name}, the workload "
                           f"expects {job['expect_engine']}")
    return session


def host_state(session) -> dict:
    import jax
    st = session.state
    return jax.device_get({"clients": list(st.clients),
                           "client_m": [o.m for o in st.client_opts],
                           "servers": list(st.servers),
                           "server_m": [o.m for o in st.server_opts]})


def first_batches(traffic, job: dict, seed: int, shards, rounds: int):
    """Each client's batches of the first ``rounds`` rounds, in the order
    the session draws them."""
    count = rounds * job["local_epochs"]
    return [traffic.first_batches(s, job["batch"], seed + i, count)
            for i, s in enumerate(shards)]


def reference_numbers(cfg: dict, work: dict, seed: int, shards,
                      program: dict, rounds: int,
                      bench_dir: str = BENCH_DIR) -> Dict[str, float]:
    """Run the plain reference over the first ``rounds`` rounds and compare
    ``program`` (losses and host state after them) with it."""
    traffic, reference, _ = cell_parts(cfg, work, bench_dir)
    job = work["job"]
    ref = reference.run_rounds(cfg["model"], job, seed,
                               first_batches(traffic, job, seed, shards,
                                             rounds), rounds)
    return check.compare(program, ref, ref["initial"])


# ------------------------------------------------------------------------ run
def device_info(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def say(*parts) -> None:
    print(*parts, flush=True)


def run(argv, t0: float, *, bench_dir: str = BENCH_DIR,
        require_chip: bool = True, edit: Optional[Callable] = None) -> int:
    """One run; returns the exit code.  ``require_chip=False`` and ``edit``
    (``edit(cfg, work)`` may change the loaded files in place) are for the
    tests, which drive a run on the CPU at a small size."""
    args = parse(argv)
    import jax

    devices = jax.devices()
    spec = benchmark(bench_dir)
    work = workload(args.workload, bench_dir)
    cfg = config(work["config"], bench_dir)
    if edit is not None:
        edit(cfg, work)
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < work["chips"]):
        print(f"bench: {args.workload} needs {work['chips']} TPU chip(s); "
              f"JAX found {len(devices)} x {devices[0].platform}",
              file=sys.stderr)
        return 2
    dev = device_info(devices)
    chip_peaks = peaks.peaks(dev["kind"]) if require_chip else None

    from repro.launch.compile_cache import enable_compile_cache
    say(f"compile cache: {enable_compile_cache()}")
    # every program, eager operations too, goes to the cache, so a second
    # run of the cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = CompileCounter()

    job = work["job"]
    chunk, seed = job["chunk_rounds"], args.seed
    N, B, E = len(job["splits"]), job["batch"], job["local_epochs"]
    traffic, _, flops = cell_parts(cfg, work, bench_dir)

    with jax.profiler.TraceAnnotation("bench.setup"):
        t = time.perf_counter()
        shards = traffic.cell_data(seed, cfg["data"], N)
        data_s = time.perf_counter() - t
        t = time.perf_counter()
        session = build_session(cfg, job, seed, shards)
        build_s = time.perf_counter() - t
        t = time.perf_counter()
        first = session.train(chunk, local_epochs=E, chunk_rounds=chunk)
        program = host_state(session)
        program["losses"] = [(m.client_loss, m.server_loss) for m in first]
        first_s = time.perf_counter() - t
        # a second call meets what the first made (the state's counters
        # come back committed to the device), so the window compiles nothing
        session.train(chunk, local_epochs=E, chunk_rounds=chunk)
    # a fixed amount of work: the window's rounds follow from --seconds and
    # the cell's rate on the chip it was calibrated on, never from a timing
    # taken in this run (a train call's fixed host cost swings by more
    # than a chunk's time, and the rate depends on the rounds per call);
    # the traced run makes the same call
    rounds = chunk * max(1, round(args.seconds * job["rounds_per_s"] / chunk))
    trace_rounds = chunk * max(1, round(TRACE_SECONDS * job["rounds_per_s"]
                                        / chunk))
    setup_s = time.perf_counter() - t0
    say(f"device: {dev['platform']} {dev['kind']} x{dev['count']}  "
        f"engine: {session.engine_name}")
    say(f"setup: data {data_s:.3f}s build {build_s:.3f}s first chunk "
        f"{first_s:.3f}s -> R={rounds}")

    counter.armed = True
    try:
        t = time.perf_counter()
        done = session.train(rounds, local_epochs=E, chunk_rounds=chunk)
        window_s = time.perf_counter() - t
        stage = dict(getattr(session.engine, "last_stage_stats", {}) or {})
        if args.trace:
            log_dir = tempfile.mkdtemp(prefix="bench-trace-")
            with trace.record(log_dir):
                with jax.profiler.TraceAnnotation(WINDOW):
                    t = time.perf_counter()
                    session.train(trace_rounds, local_epochs=E,
                                  chunk_rounds=chunk)
                    traced_s = time.perf_counter() - t
    finally:
        counter.armed = False
    compiles = counter.count
    stats = devices[0].memory_stats() or {}
    peak_bytes = stats.get("peak_bytes_in_use")
    failed = sum(1 for m in done if not (math.isfinite(m.client_loss)
                                         and math.isfinite(m.server_loss)))
    stage_share = (100.0 * stage["wait_s"] / window_s
                   if "wait_s" in stage else None)
    say(f"window: R={rounds} rounds in {window_s:.3f}s, compilations "
        f"inside the window: {compiles}, peak_bytes_in_use {peak_bytes}, "
        f"stage wait {stage_share}%")
    del session, done
    gc.collect()

    reduced = None
    if args.trace:
        t = time.perf_counter()
        reduced = trace.reduce(trace.load(log_dir), WINDOW)
        shutil.rmtree(log_dir, ignore_errors=True)
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2 ** 20
        say(f"trace: R={trace_rounds} rounds in {traced_s:.3f}s, read in "
            f"{time.perf_counter() - t:.3f}s, busy {reduced.busy_s:.4f}s of "
            f"{reduced.window_s:.4f}s, host peak {rss:.2f} GiB")

    t = time.perf_counter()
    numbers = reference_numbers(cfg, work, seed, shards, program, chunk,
                                bench_dir)
    check_s = time.perf_counter() - t
    limits = work["limits"]
    correct = check.verdict(numbers, limits)
    say("check numbers: " + json.dumps(numbers))
    numbers = {n: numbers[n] for n in limits}
    say(f"check: reference followed {chunk} rounds in {check_s:.3f}s")

    samples = rounds * N * B * E
    facts = {"rounds": rounds, "window_s": window_s, "samples": samples,
             "trace_rounds": trace_rounds, "devices": work["chips"],
             "count": flops.round_count(cfg["model"], job),
             "peaks": chip_peaks,
             "trace": reduced, "stage": stage, "peak_bytes": peak_bytes,
             "setup_s": setup_s}
    e2e = {"train_samples_per_s": samples / window_s,
           "peak_hbm_gib": (peak_bytes / 2 ** 30) if peak_bytes else None,
           "setup_s": setup_s}
    metrics = {}
    if args.trace:
        for m in cell_metrics(spec, args.workload, "per_layer"):
            value = bench_module("metrics", m["name"], bench_dir).read(facts)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell_metrics(spec, args.workload, "end_to_end"):
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    device = {**dev, "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(correct), "attempted": rounds,
              "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.top_ops(),
                               "idle_gaps": reduced.top_gaps()}
    result["check"] = {n: {"value": numbers[n], "limit": limits[n]}
                       for n in limits}
    for n in limits:
        print(f"check {n} {numbers[n]!r} limit {limits[n]!r}",
              file=sys.stderr, flush=True)
    say(json.dumps(result))
    return 0
