"""Adaptive serving CLI: a thin front-end over ``repro.api.ServeSession``.

Serves a stream of synthetic prompts through the continuous-batching
entropy-gated engine (Alg. 3): requests join fixed decode slots, each
decode tick gates at the client boundary's exit head, and the report gives
the client adoption ratio plus the server-offload compute saving — the
quantities the paper's Fig. 2 trades against accuracy.

``--boundary`` selects which exit boundary acts as the client cut.  The
gate head, the split profile, and the reported cut layer are all derived
from the one sorted source (``repro.api.serve_session.
resolve_serve_boundary``) so they cannot disagree, whatever order the
config lists its ``exit_layers`` in (tests/test_serve_boundary.py).

  PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --tau 2.0
  PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b \
      --ckpt ckpt/run1/ckpt-00000100          # serve trained weights
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro import configs as configs_mod
from repro.api.serve_session import ServeSession, resolve_serve_boundary
from repro.launch.compile_cache import enable_compile_cache
from repro.models.backbone import init_backbone


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--decode-tokens", type=int, default=32)
    ap.add_argument("--tau", type=float, default=2.0)
    ap.add_argument("--boundary", type=int, default=0,
                    help="exit boundary index used as the client cut "
                         "(indexes sorted(exit_layers))")
    ap.add_argument("--exit-policy", default="select",
                    choices=["select", "sticky"])
    ap.add_argument("--ckpt", default=None,
                    help="TrainSession checkpoint stem to serve; default "
                         "serves seed-initialized weights")
    ap.add_argument("--kernels", default="auto",
                    choices=["auto", "pallas", "ref"],
                    help="kernel backend for the routed hot sites: "
                         "auto = pallas on TPU, ref elsewhere")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs_mod.get(args.arch).smoke().with_(kernels=args.kernels)
    exits, cut, skip_frac = resolve_serve_boundary(cfg, args.boundary)
    max_len = args.prompt_len + 1 + args.decode_tokens

    if args.ckpt:
        from repro.core.backbone_splitee import BackboneSplitModel
        session = ServeSession.restore(
            args.ckpt, BackboneSplitModel(cfg, seed=args.seed),
            tau=args.tau, boundary=args.boundary, slots=args.slots,
            max_len=max_len, exit_policy=args.exit_policy)
    else:
        params = init_backbone(jax.random.PRNGKey(args.seed), cfg)
        session = ServeSession(cfg, params, tau=args.tau,
                               boundary=args.boundary, slots=args.slots,
                               max_len=max_len,
                               exit_policy=args.exit_policy)

    rng = np.random.default_rng(1)
    for _ in range(args.requests):
        session.submit(rng.integers(0, cfg.vocab_size, args.prompt_len),
                       decode_tokens=args.decode_tokens)
    session.run()

    st = session.stats
    ratio = st.adoption_ratio
    print(f"arch={cfg.name} tau={args.tau} boundary={args.boundary} "
          f"(cut layer {cut}/{cfg.num_layers}) policy={args.exit_policy}")
    print(f"served {st.requests} requests / {st.tokens} decode tokens in "
          f"{st.decode_ticks} ticks ({st.wall_s:.2f}s, "
          f"{st.tokens / max(st.wall_s, 1e-9):.1f} tok/s)  "
          f"client adoption ratio {ratio:.3f}")
    print(f"server compute skipped ~{ratio * skip_frac * 100:.1f}% of layer "
          f"work (exited tokens skip {skip_frac * 100:.0f}% of layers)")
    if args.exit_policy == "sticky":
        print(f"client-only ticks: {st.client_only_ticks}/{st.decode_ticks}")


if __name__ == "__main__":
    main()
