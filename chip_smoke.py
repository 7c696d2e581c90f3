"""Smoke run of the training and serving paths on a TPU, through the entry
points a user calls.  The weights are random, drawn from seed 0.

One chip (no arguments):

  * ``resnet18`` — the paper's heterogeneous Table-IV setting, built by
    ``launch/train.py``'s own parser and session builder: the CIFAR-10
    ResNet-18 at width 1.0 on 32x32 inputs, 12 clients cut at layers
    3,3,3,3,4,4,4,4,5,5,5,5, the averaging strategy, batch 64 per client,
    the fused engine, 3 rounds, then ``evaluate`` and ``evaluate_adaptive``;
  * ``backbone`` — the glm4-9b and rwkv6-3b smoke configs, 2 fused rounds
    each with ``kernels="pallas"`` (the compiled round must hold a native
    Mosaic ``tpu_custom_call``), whose losses must match the same run with
    ``kernels="ref"``; then the glm4 checkpoint is restored into a
    ``ServeSession`` that serves 4 requests, token for token equal to the
    sequential reference.

Four chips (``--chips 4``): ``spmd`` only — the resnet18 setting on the
spmd engine over a (lanes=2, data=2, model=1) mesh with the greedy recipe,
whose parameters and Adam moments must be sharded, against a fused run on
device 0.

Everything runs in this one process, which is the only one to touch the
chip.  Any failed check raises, so the exit code is non-zero; without a TPU
the script exits 1 before any phase.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

  python chip_smoke.py
  python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Dict, List

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec  # noqa: E402

from repro.api.serve_session import (ServeSession,  # noqa: E402
                                     sequential_reference)
from repro.launch import train as train_mod  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_lane_host_mesh  # noqa: E402

#: the paper's heterogeneous setting (Table IV), as train.py flags
RESNET_ARGV = ("--model", "resnet", "--clients", "12",
               "--splits", "3,3,3,3,4,4,4,4,5,5,5,5",
               "--strategy", "averaging", "--batch", "64",
               "--engine", "fused", "--rounds", "3")
#: the smoke backbones' training run, as train.py flags (after --arch)
BACKBONE_ARGV = ("--smoke", "--engine", "fused", "--clients", "2",
                 "--rounds", "2", "--batch", "8", "--seq-len", "16",
                 "--train-size", "64", "--test-size", "32")
BACKBONES = ("glm4_9b", "rwkv6_3b")
#: pallas-vs-ref loss bound: the routed sites' gradient tolerance
#: (tests/test_kernel_dispatch.py, wkv site)
KERNEL_ATOL, KERNEL_RTOL = 5e-3, 1e-2
#: gate-entropy bound between the serving engine and its sequential oracle
ENTROPY_TOL = 1e-4
#: spmd-vs-fused bounds on the per-round losses, absolute and relative.
#: SPMD_TOL, the engines' cross-device reduction-order tolerance, holds for
#: the first two rounds, whose losses come from the initial parameters and
#: from one update.  Later rounds get SPMD_TRAJECTORY_TOL and the trained
#: state is reported, not bounded: Adam's step is sign(g) wherever
#: |g| >> eps, so a change of reduction order moves the elements whose
#: gradient sits at rounding level by 2 * lr, and every later round spreads
#: that (at width 1.0 on a TPU v5e the third round's server loss moved by
#: 5.5e-4 while the second round's moved by 2.6e-5)
SPMD_TOL, SPMD_TRAJECTORY_TOL = 1e-4, 1e-3
#: the served request stream
SERVE_REQUESTS, SERVE_SLOTS, PROMPT_LEN, DECODE_TOKENS = 4, 2, 8, 8


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip smoke check failed: {what}")


class RoundProgram:
    """Takes the place of an engine's lazy jit of its round program (the
    ``_compile_chunk`` seam): each new argument signature is lowered and
    compiled ahead of time, so the compile seconds and the compiled HLO of
    the round are on record, and the compiled executable then runs it."""

    def __init__(self, engine):
        self.hlo: List[str] = []
        self.compile_s = 0.0
        self._compile_chunk = engine._compile_chunk
        engine._compile_chunk = self._wrap

    def _wrap(self, chunk):
        fn = self._compile_chunk(chunk)
        compiled: Dict = {}

        def call(*args):
            leaves, tree = jax.tree.flatten(args)
            key = (tree, tuple((a.shape, a.dtype) for a in leaves))
            if key not in compiled:
                t0 = time.perf_counter()
                compiled[key] = fn.lower(*args).compile()
                self.compile_s += time.perf_counter() - t0
                self.hlo.append(compiled[key].as_text())
            return compiled[key](*args)
        return call


@dataclass
class Run:
    session: object
    program: RoundProgram
    round_s: float
    test: tuple
    losses: List


def cli_args(*argv):
    """The flags as ``launch/train.py`` parses them."""
    return train_mod.build_parser().parse_args(list(argv))


def train_run(args, mesh=None) -> Run:
    """Build and train a session the way ``launch/train.py`` does: one
    round (which compiles the round program), then the rest timed."""
    check(args.rounds >= 2, "a timed run needs at least two rounds")
    arch_cfg = train_mod.resolve_arch_config(args)
    splits = train_mod.resolve_splits(args, arch_cfg)
    model, parts, _, test = train_mod.build_model_and_data(args, arch_cfg)
    session = train_mod.new_session(args, model, parts, splits, mesh=mesh)
    program = RoundProgram(session.engine)
    session.train(1)
    t0 = time.perf_counter()
    session.train(args.rounds - 1)
    round_s = (time.perf_counter() - t0) / (args.rounds - 1)
    losses = [(m.client_loss, m.server_loss) for m in session.history]
    check(len(losses) == args.rounds and np.isfinite(losses).all(),
          f"{args.rounds} rounds of finite losses, got {losses}")
    return Run(session, program, round_s, test, losses)


def peak_bytes():
    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def resnet18_phase(*extra) -> Dict:
    """The paper's model through the training entry point's builders."""
    run = train_run(cli_args(*RESNET_ARGV, *extra))
    s = run.session
    check(s.engine.name == "fused", f"fused engine, got {s.engine_name}")
    xt, yt = run.test
    ev = s.evaluate(xt, yt, batch_size=512)
    ad = s.evaluate_adaptive(xt, yt, tau=s.ctx.cfg.entropy_threshold,
                             batch_size=512)
    accs = np.concatenate([ev["client_acc"], ev["server_acc"], ad["acc"]])
    check(len(accs) == 3 * s.ctx.N and np.all((accs >= 0) & (accs <= 1)),
          f"per-client accuracies in [0, 1], got {accs}")
    return {"engine": s.engine_name, "losses": run.losses,
            "round_s": run.round_s, "compile_s": run.program.compile_s,
            "server_acc": [float(a) for a in ev["server_acc"]],
            "peak_bytes_in_use": peak_bytes()}


def serve_phase(session) -> Dict:
    """Restore ``session``'s checkpoint into a ServeSession and hold its
    continuous-batching streams to the sequential reference."""
    max_len = PROMPT_LEN + 1 + DECODE_TOKENS
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ckpt")
        session.save(path)
        serve = ServeSession.restore(path, session.model, boundary=0,
                                     slots=SERVE_SLOTS, max_len=max_len,
                                     exit_policy="select")
    rng = np.random.default_rng(0)
    for _ in range(SERVE_REQUESTS):
        serve.submit(rng.integers(0, serve.cfg.vocab_size, PROMPT_LEN),
                     decode_tokens=DECODE_TOKENS)
    results = serve.run()
    check(len(results) == SERVE_REQUESTS,
          f"{SERVE_REQUESTS} requests served, got {len(results)}")
    ent_delta = 0.0
    for r in results:
        ref = sequential_reference(serve.cfg, serve.params, r.prompt,
                                   DECODE_TOKENS, tau=serve.tau, boundary=0,
                                   max_len=max_len)
        check(r.tokens == ref.tokens,
              f"request {r.rid} tokens {r.tokens} != reference {ref.tokens}")
        check(r.exited == ref.exited, f"request {r.rid} gate decisions")
        ent_delta = max(ent_delta, float(np.max(np.abs(
            np.asarray(r.entropy) - np.asarray(ref.entropy)))))
    check(ent_delta <= ENTROPY_TOL,
          f"gate entropy delta {ent_delta} <= {ENTROPY_TOL}")
    return {"requests": len(results), "tokens": serve.stats.tokens,
            "adoption_ratio": serve.stats.adoption_ratio,
            "entropy_delta": ent_delta}


def backbone_phase(arch: str, *extra, serve: bool = False) -> Dict:
    """Pallas-routed training against the reference kernels on the same
    device, both at full f32 matmul precision; optionally serve the
    trained checkpoint."""
    runs = {}
    with jax.default_matmul_precision("highest"):
        for kn in ("pallas", "ref"):
            runs[kn] = train_run(cli_args("--arch", arch, *BACKBONE_ARGV,
                                          "--kernels", kn, *extra))
        out = {"native_kernels": any("tpu_custom_call" in h
                                     for h in runs["pallas"].program.hlo),
               "losses": runs["pallas"].losses,
               "round_s": runs["pallas"].round_s,
               "compile_s": runs["pallas"].program.compile_s}
        got, want = (np.asarray(runs[k].losses) for k in ("pallas", "ref"))
        out["loss_delta"] = float(np.max(np.abs(got - want)))
        check(np.allclose(got, want, atol=KERNEL_ATOL, rtol=KERNEL_RTOL),
              f"{arch} pallas losses {got.tolist()} vs ref {want.tolist()}")
        if serve:
            out["serve"] = serve_phase(runs["pallas"].session)
    out["peak_bytes_in_use"] = peak_bytes()
    return out


def _max_delta(a, b) -> float:
    return max(float(np.max(np.abs(np.asarray(u, np.float64)
                                   - np.asarray(v, np.float64))))
               for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _axes(spec, i: int):
    """The mesh axes a PartitionSpec puts dim ``i`` on."""
    ax = spec[i] if i < len(spec) else None
    return () if ax is None else (ax if isinstance(ax, tuple) else (ax,))


def spmd_phase(*extra) -> Dict:
    """The resnet18 setting on the spmd engine over the (2, n/2, 1)
    lanes/data/model mesh against the fused engine on one device."""
    with jax.default_matmul_precision("highest"):
        fused = train_run(cli_args(*RESNET_ARGV, *extra))
        spmd = train_run(cli_args(*RESNET_ARGV, "--engine", "spmd",
                                  "--recipe", "greedy", "--lanes", "2",
                                  *extra), mesh=make_lane_host_mesh(2))
    eng, st = spmd.session.engine, spmd.session.state
    check(eng.name == "spmd", f"spmd engine, got {spmd.session.engine_name}")
    # params and Adam moments really are sharded: every leaf of the
    # engine-placed carry holds exactly the slice its recipe spec implies
    carry = eng._stack_carry(list(st.clients), list(st.client_opts),
                             list(st.servers), list(st.server_opts))
    specs = jax.tree.leaves(eng._carry_specs,
                            is_leaf=lambda x: isinstance(x, PartitionSpec))
    leaves = jax.tree.leaves(carry)
    check(len(specs) == len(leaves), "one recipe spec per carry leaf")
    sizes = dict(eng.mesh.shape)
    held = 0
    for leaf, spec in zip(leaves, specs):
        want = tuple(d // int(np.prod([sizes[a] for a in _axes(spec, i)]))
                     for i, d in enumerate(leaf.shape))
        got = leaf.addressable_shards[0].data.shape
        check(got == want, f"leaf {leaf.shape} spec {spec}: shard {got}, "
                           f"expected {want}")
        held += int(np.prod(got))
    params = [p for c, _, s, _ in carry.values()
              for p in jax.tree.leaves((c["trainable"], s["trainable"]))]
    moments = [m for _, co, _, so in carry.values()
               for m in jax.tree.leaves((co.m, so.m))]
    check([p.addressable_shards[0].data.shape for p in params]
          == [m.addressable_shards[0].data.shape for m in moments],
          "Adam moments sharded like their params")
    max_split = max(p.size // p.addressable_shards[0].data.size
                    for p in params)
    state_delta = _max_delta(fused.session.state, st)
    got, want = np.asarray(spmd.losses), np.asarray(fused.losses)
    round_delta = np.abs(got - want).max(axis=1)
    what = (f"spmd losses {got.tolist()} vs fused {want.tolist()} (state "
            f"delta {state_delta})")
    check(np.allclose(got[:2], want[:2], atol=SPMD_TOL, rtol=SPMD_TOL),
          f"first two rounds within {SPMD_TOL}: {what}")
    check(np.allclose(got, want, atol=SPMD_TRAJECTORY_TOL,
                      rtol=SPMD_TRAJECTORY_TOL),
          f"every round within {SPMD_TRAJECTORY_TOL}: {what}")
    return {"devices": eng.mesh.size, "mesh": sizes, "max_split": max_split,
            "carry_fraction_per_device":
                held / sum(leaf.size for leaf in leaves),
            "state_delta": state_delta,
            "round_loss_delta": round_delta.tolist(),
            "spmd_round_s": spmd.round_s, "fused_round_s": fused.round_s,
            "spmd_compile_s": spmd.program.compile_s,
            "fused_compile_s": fused.program.compile_s}


def report(phase: str, result: Dict) -> None:
    print(f"[{phase}] " + json.dumps(result, default=str), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the resnet18 and backbone phases; 4: the spmd "
                         "engine against the fused one, and nothing else")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"visible", file=sys.stderr)
        return 1
    enable_compile_cache()
    print(f"devices: {len(devices)} x {devices[0].device_kind}", flush=True)

    if args.chips == 1:
        report("resnet18", resnet18_phase())
        for arch in BACKBONES:
            result = backbone_phase(arch, serve=arch == "glm4_9b")
            report(f"backbone {arch}", result)
            check(result["native_kernels"],
                  f"{arch}: no tpu_custom_call in the compiled round — the "
                  f"Pallas kernels did not lower natively")
    else:
        result = spmd_phase()
        report("spmd", result)
        check(result["max_split"] == result["devices"],
              f"no parameter is split over all {result['devices']} devices "
              f"(largest split {result['max_split']})")

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
