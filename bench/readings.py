"""The readings the correctness limits are set from, on the chip, at the
cell's own size, in one process:

  * the program: for each seed of ``--seeds``, the cell's first chunk of
    rounds through ``TrainSession`` (as a run's set-up trains it) against
    the plain float32 reference: the lower readings;
  * the control: for each seed of ``--control-seeds``, the reference in
    bfloat16 put in the program's place, against the float32 reference;
  * the faults: for the same seeds, the reference with each planted fault
    of ``bench/reference`` put in the program's place.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 4,5,6

One JSON line per reading on stdout.  No measured window: training's
readings need none.
"""
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402

from bench import check, harness  # noqa: E402


def seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        print("readings: needs a TPU", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    work = harness.workload(args.workload)
    cfg = harness.config(work["config"])
    traffic, reference, _ = harness.cell_parts(cfg, work)
    job = work["job"]
    chunk, E = job["chunk_rounds"], job["local_epochs"]
    N = len(job["splits"])

    def emit(kind, seed, numbers, t0):
        print(json.dumps({"workload": args.workload, "kind": kind,
                          "seed": seed, "seconds": time.perf_counter() - t0,
                          **numbers}), flush=True)

    for seed in args.seeds:
        t0 = time.perf_counter()
        shards = traffic.cell_data(seed, cfg["data"], N)
        session = harness.build_session(cfg, job, seed, shards)
        first = session.train(chunk, local_epochs=E, chunk_rounds=chunk)
        program = harness.host_state(session)
        program["losses"] = [(m.client_loss, m.server_loss) for m in first]
        del session
        gc.collect()
        numbers = harness.reference_numbers(cfg, work, seed, shards,
                                            program, chunk)
        emit("program", seed, numbers, t0)

    faults = [f for f in reference.FAULTS
              if f != "noagg" or job["strategy"] == "averaging"]
    for seed in args.control_seeds:
        t0 = time.perf_counter()
        shards = traffic.cell_data(seed, cfg["data"], N)
        batches = harness.first_batches(traffic, job, seed, shards, chunk)
        net = cfg["model"]
        ref = reference.run_rounds(net, job, seed, batches, chunk)
        emit("reference", seed, {}, t0)
        for kind, dtype, fault in ([("control", jnp.bfloat16, None)]
                                   + [(f, jnp.float32, f) for f in faults]):
            t0 = time.perf_counter()
            other = reference.run_rounds(net, job, seed, batches, chunk,
                                         dtype=dtype, fault=fault)
            emit(kind, seed, check.compare(other, ref, ref["initial"]), t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
