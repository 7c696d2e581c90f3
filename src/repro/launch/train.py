"""Training driver: THE training entry point of the repo, built on
``repro.api.TrainSession`` with the mesh-sharded ``"spmd"`` engine.

Every scale runs the same code path:
  * host demo (this container): ``--host-devices 4`` forces fake CPU
    devices before jax initializes, the engine builds the default data
    mesh over them, and the global batch shards across the ``data`` axis —
    actually executes, and is cross-checked against the reference engine
    by tests/test_spmd_engine.py.
  * production: ``--mesh single|multi`` builds the 256/512-chip mesh from
    ``launch.mesh.make_production_mesh`` and hands it to the session
    (``TrainSession(..., mesh=...)``).
  * one device, no mesh: ``--engine auto`` degrades to the fused engine
    and says why (the ``engine_name`` selection note).

Sharding is recipe-driven (``launch/shardings.py``): ``--recipe
{greedy,megatron,hybrid,fsdp-off,replicate}`` picks how parameters and
Adam moments spread over the mesh (FSDP over the data axis by default;
"replicate" is batch-only sharding), and ``--lanes N`` factors a cohort-
lane axis out of the data axis so stacked cohort lanes shard instead of
replicating — e.g. ``--host-devices 4 --lanes 2`` splits each two-client
cohort over two devices and each lane's batch over the other two.

Checkpointing is the session's periodic-save policy: ``--save-every N``
rotates ``ckpt-<round>`` pairs under ``--checkpoint-dir`` (keep-last-k),
and ``--resume`` picks the run back up from the newest valid checkpoint
via ``TrainSession.restore_latest``.

Besides the paper-scale ``--model mlp|resnet`` adapters, ``--arch <name>``
trains any registered ``configs/`` backbone (GLM-4, DeepSeek-V3, Qwen3-MoE,
RWKV6, Whisper, …) through the same session facade: the architecture module
is resolved via ``repro.configs.get(name)``, ``--smoke`` picks its reduced
``smoke()`` variant (the full ``config()`` otherwise), and the model is the
``BackboneSplitModel`` adapter over a synthetic sequence-classification
token stream.  Cut layers must sit at the config's ``exit_layers``
(``--splits`` defaults to cycling them across clients).

Example (4 fake host devices, spmd engine, resumable):
  PYTHONPATH=src python -m repro.launch.train --model mlp --clients 4 \
      --rounds 20 --host-devices 4 --checkpoint-dir /tmp/run \
      --save-every 5 --resume

Example (GLM-4 smoke backbone, fused engine):
  PYTHONPATH=src python -m repro.launch.train --arch glm4_9b --smoke \
      --engine fused --clients 4 --rounds 5 --batch 16 \
      --train-size 256 --test-size 64 --checkpoint-dir /tmp/glm4
"""
from __future__ import annotations

import glob
import os

# must run before jax initializes: fake host devices for the spmd engine,
# and the multi-host XLA flags + coordinator options for --distributed
from repro.launch import distributed as distributed_mod
from repro.launch.hostdevices import force_host_devices

force_host_devices("--host-devices")
_DIST = distributed_mod.setup_from_argv()

import argparse
import json
import time

import jax
import numpy as np

from repro import configs as configs_mod
from repro import obs
from repro.api import TrainSession
from repro.config import HeteroProfile, OptimizerConfig, SplitEEConfig
from repro.configs import resnet18_cifar
from repro.core.backbone_splitee import BackboneSplitModel
from repro.core.splitee import MLPSplitModel, ResNetSplitModel
from repro.data.pipeline import ClientPartitioner
from repro.data.synthetic import SyntheticImageDataset, SyntheticSeqClsDataset
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_lane_host_mesh, make_production_mesh
from repro.launch.shardings import NAMED_RECIPES

#: default hetero cut layers per model family (paper Table I spirit:
#: clients split shallow/mid/deep)
DEFAULT_SPLITS = {"mlp": (1, 2, 3), "resnet": (3, 4, 5)}

#: CLI knobs that shape the regenerated dataset / model / session; a resumed
#: run must match every one of them or it would silently replay a different
#: data stream — or, for ``arch``/``grad_mode``, silently continue a
#: checkpoint into a *different network or gradient math* (driver.json
#: sidecar next to the checkpoints)
DATA_KNOBS = ("model", "arch", "smoke", "seq_len", "clients", "splits",
              "strategy", "aggregate_every", "batch", "grad_mode", "seed",
              "train_size", "test_size", "population", "dirichlet_alpha",
              "participation_rate", "churn_seed", "straggler_rate")


def driver_knobs(args, splits) -> dict:
    d = {k: getattr(args, k) for k in DATA_KNOBS if k != "splits"}
    d["splits"] = list(splits)
    return d


def check_driver_sidecar(ckpt_dir: str, args, splits) -> None:
    """Fail loudly when a resumed run regenerates its data/model from
    different knobs than the saved one (the session manifest cannot see
    dataset-shaping flags like --train-size — the sidecar can)."""
    path = os.path.join(ckpt_dir, "driver.json")
    if not os.path.exists(path):
        return                      # checkpoints written by library code
    with open(path) as f:
        saved = json.load(f)
    now = driver_knobs(args, splits)
    for k in DATA_KNOBS:
        if k in saved and saved[k] != now[k]:
            raise SystemExit(
                f"--resume mismatch: checkpoint dir was written with "
                f"--{k.replace('_', '-')}={saved[k]!r} but this run has "
                f"{now[k]!r}")


def resolve_arch_config(args):
    """The --arch run's ModelConfig (a cheap dataclass — no parameter
    init yet), or None for the mlp/resnet families."""
    if not args.arch:
        return None
    try:
        mod = configs_mod.get(args.arch)
    except ValueError as e:
        raise SystemExit(f"--arch: {e}") from None
    cfg = mod.smoke() if args.smoke else mod.config()
    return cfg.with_(kernels=getattr(args, "kernels", "auto"))


def build_model_and_data(args, arch_cfg):
    """(SplitModel adapter, train shards, train (x, y), held-out (x, y)).
    The raw train split rides along so --population can re-partition it
    non-IID across the simulated pool instead of using the IID shards."""
    if arch_cfg is not None:
        cfg = arch_cfg
        model = BackboneSplitModel(cfg, seed=args.seed)
        ds = SyntheticSeqClsDataset(
            vocab_size=cfg.vocab_size, seq_len=args.seq_len,
            num_classes=min(8, cfg.vocab_size),
            train_size=args.train_size, test_size=args.test_size,
            seed=args.seed)
        x, y = ds.train
        xt, yt = ds.test
    elif args.model == "mlp":
        rng = np.random.default_rng(args.seed)
        classes, d = 5, 32
        centers = rng.normal(size=(classes, d)) * 2.0
        y = rng.integers(0, classes, args.train_size + args.test_size)
        y = y.astype(np.int32)
        x = (centers[y] + rng.normal(size=(len(y), d))).astype(np.float32)
        xt, yt = x[args.train_size:], y[args.train_size:]
        x, y = x[:args.train_size], y[:args.train_size]
        model = MLPSplitModel(in_dim=d, hidden=64, num_classes=classes,
                              num_layers=6, seed=args.seed)
    else:
        # the paper's CIFAR-10 ResNet-18 at full width (--smoke: the reduced
        # width of configs/resnet18_cifar.smoke())
        rcfg = (resnet18_cifar.smoke() if args.smoke
                else resnet18_cifar.config("cifar10"))
        ds = SyntheticImageDataset(num_classes=rcfg.num_classes,
                                   train_size=args.train_size,
                                   test_size=args.test_size,
                                   image_size=rcfg.image_size, noise=2.0,
                                   seed=args.seed)
        x, y = ds.train
        xt, yt = ds.test
        model = ResNetSplitModel(rcfg, seed=args.seed)
    parts = ClientPartitioner(args.clients, seed=args.seed).split(x, y)
    return model, parts, (x, y), (xt, yt)


def build_population(args, splits, x, y):
    """The --population pool: a Dirichlet-skewed partition of the train
    split over ``--population`` simulated clients, split points cycling
    the slot layout, with the seeded churn/straggler process from the
    CLI knobs (all recorded in DATA_KNOBS for resume validation)."""
    from repro.population import ClientPopulation
    try:
        return ClientPopulation.dirichlet(
            x, y, num_clients=args.population, slot_splits=splits,
            alpha=args.dirichlet_alpha, seed=args.seed,
            participation_rate=args.participation_rate,
            churn_seed=args.churn_seed,
            straggler_rate=args.straggler_rate,
            min_shard=args.batch)
    except ValueError as e:
        raise SystemExit(f"--population: {e}") from None


def resolve_splits(args, arch_cfg) -> tuple:
    """The per-client cut layers: ``--splits``, else the family's depths
    (an arch's exit layers) cycled over ``--clients``."""
    if args.splits:
        splits = tuple(int(s) for s in args.splits.split(","))
    elif arch_cfg is not None:
        cuts = tuple(sorted(arch_cfg.exit_layers))   # the valid cut layers
        splits = tuple(cuts[i % len(cuts)] for i in range(args.clients))
    else:
        splits = tuple(DEFAULT_SPLITS[args.model][i % 3]
                       for i in range(args.clients))
    if len(splits) != args.clients:
        raise SystemExit(f"--splits names {len(splits)} clients but "
                         f"--clients is {args.clients}")
    if arch_cfg is not None:
        bad = sorted(set(splits) - set(arch_cfg.exit_layers))
        if bad:
            raise SystemExit(
                f"--splits {bad} are not exit boundaries of "
                f"{arch_cfg.name}; valid cut layers: "
                f"{sorted(arch_cfg.exit_layers)}")
    return splits


def new_session(args, model, parts, splits, mesh=None, population=None):
    """A fresh ``TrainSession`` from the CLI knobs (``parts`` is ignored
    when a client ``population`` supplies the data)."""
    splitee_cfg = SplitEEConfig(profile=HeteroProfile(splits),
                                strategy=args.strategy,
                                aggregate_every=args.aggregate_every,
                                entropy_threshold=args.tau)
    opt_cfg = OptimizerConfig(
        lr=args.lr, warmup_steps=0,
        total_steps=max(args.rounds * args.local_epochs, 1) + 16)
    return TrainSession.from_config(
        model, splitee_cfg, opt_cfg,
        None if population is not None else parts,
        batch_size=args.batch,
        engine=args.engine, seed=args.seed, mesh=mesh,
        grad_mode=args.grad_mode, recipe=args.recipe,
        population=population)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="mlp", choices=["mlp", "resnet"])
    ap.add_argument("--arch", default="",
                    help="train a configs/ backbone (e.g. glm4_9b, "
                         "qwen3-moe-235b-a22b) through BackboneSplitModel; "
                         "overrides --model")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke() config instead of the "
                         "full-scale config() (--arch, and --model resnet: "
                         "width 0.125 instead of the paper's 1.0)")
    ap.add_argument("--seq-len", type=int, default=16,
                    help="with --arch: synthetic token sequence length")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--splits", default="",
                    help="comma-separated cut layer per client (default: "
                         "cycle the model family's depths)")
    ap.add_argument("--strategy", default="averaging",
                    choices=["averaging", "distributed", "sequential"])
    ap.add_argument("--aggregate-every", type=int, default=1)
    ap.add_argument("--rounds", type=int, default=20,
                    help="total rounds the run should reach (a resumed run "
                         "trains only the remainder)")
    ap.add_argument("--local-epochs", type=int, default=1)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--engine", default="auto",
                    choices=["auto", "spmd", "fused", "reference"])
    ap.add_argument("--grad-mode", default="eq1", choices=["eq1", "sum"])
    ap.add_argument("--kernels", default="auto",
                    choices=["auto", "pallas", "ref"],
                    help="kernel backend for the routed hot sites "
                         "(attention, wkv, entropy gate) with --arch: "
                         "auto = pallas on TPU, ref elsewhere.  Layout-"
                         "only — equivalence-gated, so not a resume knob")
    ap.add_argument("--mesh", default="auto",
                    choices=["auto", "single", "multi"],
                    help="auto: engine default over visible devices; "
                         "single/multi: the production TPU mesh")
    ap.add_argument("--recipe", default=None,
                    choices=sorted(NAMED_RECIPES),
                    help="spmd sharding recipe (launch/shardings.py): how "
                         "cohort lanes, params and Adam moments spread "
                         "over the mesh; 'replicate' is batch-only "
                         "sharding.  Default: 'greedy' for fresh runs, "
                         "the checkpoint's saved recipe on --resume")
    ap.add_argument("--lanes", type=int, default=1,
                    help="factor a cohort-lane axis of this size out of "
                         "the mesh's data axis (shards stacked cohort "
                         "lanes instead of replicating them)")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force N fake CPU devices (consumed pre-import)")
    ap.add_argument("--distributed", action="store_true",
                    help="multi-host run: jax.distributed.initialize "
                         "before training, meshes over the global device "
                         "list (consumed pre-argparse; env fallbacks "
                         "REPRO_DISTRIBUTED/REPRO_COORDINATOR/...)")
    ap.add_argument("--coordinator", default=None,
                    help="coordinator address host:port for --distributed "
                         "(implies it); unset = jax cluster auto-detection")
    ap.add_argument("--num-processes", type=int, default=None,
                    help="total process count for --distributed")
    ap.add_argument("--process-id", type=int, default=None,
                    help="this process's rank for --distributed")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--save-every", type=int, default=0)
    ap.add_argument("--keep-last", type=int, default=3)
    ap.add_argument("--resume", action="store_true",
                    help="continue from the newest valid checkpoint in "
                         "--checkpoint-dir (restores params, Adam moments, "
                         "the round counter, and the data cursors)")
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--train-size", type=int, default=4096)
    ap.add_argument("--test-size", type=int, default=1024)
    ap.add_argument("--tau", type=float, default=0.5,
                    help="entropy threshold for the adaptive eval")
    ap.add_argument("--population", type=int, default=0,
                    help="simulate a pool of this many clients over the "
                         "--clients cohort slots (0 = fixed cohort): "
                         "non-IID Dirichlet shards, seeded churn and "
                         "stragglers, in-graph participation masks "
                         "(docs/ENGINES.md 'Client populations')")
    ap.add_argument("--dirichlet-alpha", type=float, default=0.5,
                    help="label-skew concentration of the population's "
                         "Dirichlet partition (small = heavy skew)")
    ap.add_argument("--participation-rate", type=float, default=1.0,
                    help="per-round probability each cohort slot "
                         "participates (partial participation)")
    ap.add_argument("--churn-seed", type=int, default=0,
                    help="seed of the availability/assignment/straggler "
                         "schedule (independent of --seed so churn can "
                         "vary against a fixed data stream)")
    ap.add_argument("--straggler-rate", type=float, default=0.0,
                    help="per-round probability an assigned client "
                         "exceeds its deadline and is masked out of "
                         "aggregation")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)

    # before any jax computation: join the multi-host cluster so every
    # mesh below spans the global device list
    distributed_mod.maybe_initialize(_DIST)
    enable_compile_cache()
    coordinator = distributed_mod.is_coordinator()

    arch_cfg = resolve_arch_config(args)
    splits = resolve_splits(args, arch_cfg)

    resuming = bool(args.resume and args.checkpoint_dir and glob.glob(
        os.path.join(args.checkpoint_dir, "ckpt-*.json")))
    if resuming:
        # before any (possibly full-scale) parameter init: a knob mismatch
        # must die on the string comparison, not after materializing the
        # model and dataset
        check_driver_sidecar(args.checkpoint_dir, args, splits)

    model, parts, (x, y), (xt, yt) = build_model_and_data(args, arch_cfg)
    population = (build_population(args, splits, x, y)
                  if args.population > 0 else None)
    try:
        if args.mesh != "auto":
            mesh = make_production_mesh(multi_pod=args.mesh == "multi",
                                        lanes=args.lanes)
        elif args.lanes > 1:
            mesh = make_lane_host_mesh(args.lanes)
        else:
            mesh = None
    except ValueError as e:
        raise SystemExit(f"--lanes: {e}") from None

    resumed = False
    if resuming:
        # checkpoints exist, so --resume must resume or die — a failure
        # here (all pairs unreadable, wrong engine for this host, ...)
        # must never silently start a fresh run whose rotation would then
        # delete the real checkpoints
        try:
            session = TrainSession.restore_latest(
                args.checkpoint_dir, model,
                None if population is not None else parts,
                engine=args.engine, mesh=mesh, recipe=args.recipe,
                population=population)
        except Exception as e:                            # noqa: BLE001
            raise SystemExit(
                f"--resume: cannot restore from {args.checkpoint_dir!r}: "
                f"{e}") from e
        resumed = True
        # the restored session replays its own saved config; the CLI data
        # stream is rebuilt from the flags, so a knob mismatch would
        # silently train on different data — fail loudly instead
        for knob, want, have in (
                ("seed", session.ctx.seed, args.seed),
                ("batch", session.ctx.batch_size, args.batch),
                ("grad-mode", session.ctx.grad_mode, args.grad_mode),
                ("strategy", session.ctx.strategy, args.strategy),
                ("splits", tuple(session.ctx.profile.split_layers), splits)):
            if want != have:
                raise SystemExit(
                    f"--resume mismatch: checkpoint was written with "
                    f"{knob}={want!r} but this run has {knob}={have!r}")
    else:
        session = new_session(args, model, parts, splits, mesh=mesh,
                              population=population)

    what = (f"arch={args.arch}{' (smoke)' if args.smoke else ''} "
            f"[{model.name}]" if args.arch else f"model={args.model}")
    print(f"{what}  clients={args.clients}  splits={splits}  "
          f"strategy={args.strategy}  grad_mode={args.grad_mode}"
          + (f"  population={args.population} "
             f"(alpha={args.dirichlet_alpha}, "
             f"rate={args.participation_rate}, "
             f"stragglers={args.straggler_rate}, "
             f"churn_seed={args.churn_seed})"
             if population is not None else ""))
    print(f"devices={len(jax.devices())}"
          + (f" ({jax.process_count()} processes, "
             f"rank {jax.process_index()})"
             if jax.process_count() > 1 else "")
          + f"  engine={session.engine_name}"
          + (f"  recipe={session.ctx.recipe_name}"
             if session.engine.name == "spmd" else "")
          + (f"  [resumed at round {session.round}]" if resumed else ""))

    # checkpoints and sidecars are shared-filesystem side effects: only
    # the coordinator process writes them (every process still restores).
    # Every rank still runs the identical save_every segmentation below —
    # each engine.run() segment dispatches the same jit/collective
    # sequence on every process (chunk plans, the spmd carry fetch), so
    # ranks must not diverge in how the run is cut up; the file write
    # itself is gated on process 0 inside TrainSession._save_rotating.
    ckpt_dir = args.checkpoint_dir
    if ckpt_dir and coordinator:
        os.makedirs(ckpt_dir, exist_ok=True)
        with open(os.path.join(ckpt_dir, "driver.json"), "w") as f:
            json.dump(driver_knobs(args, splits), f, indent=1)

    remaining = args.rounds - session.round
    if remaining <= 0:
        print(f"checkpoint already at round {session.round} >= "
              f"--rounds {args.rounds}; nothing to train")
    else:
        # no --save-every but a checkpoint dir: save once at completion
        # (same segmentation on every rank; only process 0 writes files)
        save_every = (args.save_every or remaining) if ckpt_dir else 0
        t0 = time.time()
        session.train(remaining, local_epochs=args.local_epochs,
                      log_every=args.log_every,
                      save_every=save_every,
                      save_dir=ckpt_dir or None,
                      keep_last=args.keep_last)
        dt = time.time() - t0
        m = session.history[-1]
        print(f"trained {remaining} rounds in {dt:.1f}s "
              f"({remaining / dt:.2f} rounds/s)  "
              f"client_loss {m.client_loss:.4f}  "
              f"server_loss {m.server_loss:.4f}")
        shares = obs.phase_shares(session.engine.last_run_stats)
        print("phases of the last engine run (% of repro.train): "
              + "  ".join(f"{k[len('repro.'):]} {v:.1f}%"
                          for k, v in shares))
        if population is not None:
            # aggregate over THIS invocation's rounds (the engine's
            # last_participation_stats covers only the final save_every
            # segment when checkpointing splits the run)
            ms = session.history[-remaining:]
            actives = [m.active_clients for m in ms]
            slots = len(splits)
            print(f"participation: active "
                  f"{float(np.mean(actives)):.2f}/{slots} slots per round  "
                  f"stragglers {sum(m.stragglers for m in ms)}  "
                  f"masked {remaining * slots - sum(actives)} "
                  f"(pool of {population.num_clients})")
        if ckpt_dir and coordinator:
            print(f"checkpoints -> {ckpt_dir} "
                  f"(newest: round {session.round})")

    ev = session.evaluate(xt, yt, batch_size=512)
    ad = session.evaluate_adaptive(xt, yt, tau=args.tau, batch_size=512)
    for i, li in enumerate(splits):
        print(f"client {i} (l_i={li}): client_acc {ev['client_acc'][i]:.3f}  "
              f"server_acc {ev['server_acc'][i]:.3f}  "
              f"adaptive_acc {ad['acc'][i]:.3f} "
              f"(client_ratio {ad['client_ratio'][i]:.2f})")


if __name__ == "__main__":
    main()
