"""Fused SPMD Hetero-SplitEE train/serve steps for the production backbone.

This is the *scalable* formulation of the paper (docs/DESIGN.md §2): client
groups
tile the batch (and hence the ``data`` mesh axis); every shard runs the full
network; the paper's gradient routing appears as per-example stop-gradients
at the split boundaries (in ``models/backbone.py``), and Eq. (1) cross-layer
aggregation appears as per-layer gradient normalization over participation
counts.

Two gradient modes:
  * ``eq1``  (paper-faithful): client-family and server-family gradients are
    pulled separately through one shared forward (two VJP passes) and each
    layer's gradient is normalized by its participation count —
    1/|{g : l_g > l}| for the client family, 1/|C_l| for the server family —
    which is exactly the every-round FedAvg limit of Algorithm 2.
  * ``sum`` (beyond-paper optimized): one backward pass of the summed loss,
    no per-layer renormalization.  Halves backward FLOPs; recorded separately
    in docs/EXPERIMENTS.md §Perf.

The step functions are pure and jit/pjit-friendly; ``launch/dryrun.py`` and
``launch/serve.py`` wrap them in ``jax.jit`` with mesh shardings.

This module also hosts the **TrainState-boundary** cohort step
(:func:`make_cohort_train_step`): the same client/server split semantics
expressed over the ``{"trainable", "state"}`` state dicts of the
``repro.api`` engine contract, with the two gradient modes above.  The
fused engine vmaps it over cohort lanes on one device; the spmd engine
stages the identical step under a jit whose batch dimension is sharded
over the mesh's ``data`` axis (``repro.api.spmd_engine``).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import (HeteroProfile, ModelConfig, OptimizerConfig,
                          SplitEEConfig, TrainConfig)
from repro.core.aggregation import participation_counts
from repro.core.losses import accuracy, softmax_cross_entropy, softmax_entropy
from repro.kernels import dispatch
from repro.models.backbone import BackboneOutput, backbone_forward, build_plan
from repro.optim import adam_update, make_schedule


# ---------------------------------------------------------------------------
# split-id assignment
# ---------------------------------------------------------------------------


def boundary_ids_for_batch(profile: HeteroProfile, cfg: ModelConfig,
                           batch: int) -> jnp.ndarray:
    """Per-example boundary index: group g (g-th contiguous slice of the
    batch) gets the boundary index of its split layer.  Split layers must be
    members of ``cfg.exit_layers``."""
    bounds = {l: b for b, l in enumerate(sorted(cfg.exit_layers))}
    ids = []
    per = batch // profile.num_groups
    rem = batch - per * profile.num_groups
    for g, li in enumerate(profile.split_layers):
        n = per + (1 if g < rem else 0)
        ids.extend([bounds[li]] * n)
    return jnp.asarray(ids, jnp.int32)


# ---------------------------------------------------------------------------
# per-layer participation scale trees (the Eq. 1 normalization)
# ---------------------------------------------------------------------------


def _bc(vals, leaf):
    """Broadcast a per-layer (length,) vector against a stacked leaf."""
    v = jnp.asarray(vals, jnp.float32)
    return v.reshape((-1,) + (1,) * (leaf.ndim - 1))


def participation_scale_trees(params: Any, cfg: ModelConfig,
                              profile: HeteroProfile) -> Tuple[Any, Any]:
    """Returns (client_scale, server_scale) pytrees shaped like ``params``.

    scale = 1/#participants for the family that trains the leaf, 0 when the
    family never reaches it (so scaled grads are exact, not just masked)."""
    N = profile.num_groups
    n_client, n_server = participation_counts(profile.split_layers,
                                              cfg.num_layers)
    inv = lambda n: (1.0 / n) if n > 0 else 0.0
    plan = build_plan(cfg)

    def zeros_like_scales(tree, val):
        return jax.tree.map(lambda _: jnp.float32(val), tree)

    cs: Dict[str, Any] = {}
    ss: Dict[str, Any] = {}
    # embedding / frontend: reached by every group's exit loss, never by the
    # server family (stop-gradient sits after them on every example's path).
    for key in ("embed", "frontend"):
        if key in params:
            cs[key] = zeros_like_scales(params[key], inv(N))
            ss[key] = zeros_like_scales(params[key], 0.0)
    if "shared_attn" in params:
        # Zamba2's shared block occurs on both sides of every cut; both
        # families touch it.  Use 1/N for each (documented approximation).
        cs["shared_attn"] = zeros_like_scales(params["shared_attn"], inv(N))
        ss["shared_attn"] = zeros_like_scales(params["shared_attn"], inv(N))

    cs_seg, ss_seg = [], []
    for si, seg in enumerate(plan):
        cs_runs, ss_runs = [], []
        for ri, run in enumerate(seg):
            p = params["segments"][si][ri]
            if run.shared:
                cs_runs.append({})
                ss_runs.append({})
                continue
            layers = range(run.start, run.start + run.length)
            cvals = [inv(n_client[l]) for l in layers]
            svals = [inv(n_server[l]) for l in layers]
            if run.length == 1:
                cs_runs.append(zeros_like_scales(p, cvals[0]))
                ss_runs.append(zeros_like_scales(p, svals[0]))
            else:
                cs_runs.append(jax.tree.map(lambda leaf: _bc(cvals, leaf), p))
                ss_runs.append(jax.tree.map(lambda leaf: _bc(svals, leaf), p))
        cs_seg.append(cs_runs)
        ss_seg.append(ss_runs)
    cs["segments"], ss["segments"] = cs_seg, ss_seg

    if "exit_heads" in params:
        exits = sorted(cfg.exit_layers)
        cs_heads, ss_heads = [], []
        for b, l in enumerate(exits):
            cnt = sum(1 for s in profile.split_layers if s == l)
            cs_heads.append(zeros_like_scales(params["exit_heads"][b], inv(cnt)))
            ss_heads.append(zeros_like_scales(params["exit_heads"][b], 0.0))
        cs["exit_heads"], ss["exit_heads"] = cs_heads, ss_heads

    cs["head"] = zeros_like_scales(params["head"], 0.0)
    ss["head"] = zeros_like_scales(params["head"], inv(N))
    return cs, ss


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------


def hetero_losses(out: BackboneOutput, labels: jnp.ndarray,
                  split_ids: jnp.ndarray, num_boundaries: int
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, Dict[str, jnp.ndarray]]:
    """(client_total, server_total, metrics).  ``client_total`` sums each
    boundary's masked-mean exit CE (one term per client group family);
    ``server_total`` is the final-head CE over all examples."""
    client_total = jnp.zeros((), jnp.float32)
    metrics: Dict[str, jnp.ndarray] = {}
    for b in range(num_boundaries):
        mask = (split_ids == b).astype(jnp.float32)
        if labels.ndim == 2:                       # (B, T) token labels
            m = mask[:, None] * jnp.ones_like(labels, jnp.float32)
        else:
            m = mask
        ce = softmax_cross_entropy(out.exit_logits[b], labels, m)
        ce = jnp.where(jnp.sum(mask) > 0, ce, 0.0)
        client_total = client_total + ce
        metrics[f"client_loss/b{b}"] = ce
    server_loss = softmax_cross_entropy(out.logits, labels)
    metrics["server_loss"] = server_loss
    metrics["aux_loss"] = out.aux_loss
    return client_total, server_loss + out.aux_loss, metrics


# ---------------------------------------------------------------------------
# train step factory
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class StepConfig:
    model: ModelConfig
    splitee: SplitEEConfig
    train: TrainConfig
    grad_mode: str = "eq1"            # "eq1" | "sum"


def make_train_step(sc: StepConfig) -> Callable:
    """Builds ``train_step(params, opt_state, batch) -> (params, opt_state,
    metrics)``.  ``batch`` = {"tokens": (B,T), "labels": (B,T),
    "split_ids": (B,), ["embeds"/"enc": ...]}."""
    cfg = sc.model
    nb = len(cfg.exit_layers)
    schedule = make_schedule(sc.train.optimizer)
    remat = sc.train.remat != "none"

    def fwd_losses(params, batch):
        out = backbone_forward(params, cfg, tokens=batch.get("tokens"),
                               embeds=batch.get("embeds"),
                               enc=batch.get("enc"),
                               split_ids=batch["split_ids"], remat=remat)
        return hetero_losses(out, batch["labels"], batch["split_ids"], nb)

    def train_step(params, opt_state, batch):
        if sc.grad_mode == "eq1":
            def both(p):
                c, s, m = fwd_losses(p, batch)
                return jnp.stack([c, s]), m
            (losses, metrics), vjp = _vjp_aux(both, params)
            g_client = vjp(jnp.array([1.0, 0.0], jnp.float32))
            g_server = vjp(jnp.array([0.0, 1.0], jnp.float32))
            cs, ss = participation_scale_trees(params, cfg, sc.splitee.profile)
            grads = jax.tree.map(lambda gc, gs, a, b: gc * a + gs * b,
                                 g_client, g_server, cs, ss)
        else:
            def total(p):
                c, s, m = fwd_losses(p, batch)
                return c + s, m
            (loss, metrics), grads = jax.value_and_grad(total, has_aux=True)(params)

        lr = schedule(opt_state.step)
        new_params, new_opt = adam_update(params, grads, opt_state,
                                          sc.train.optimizer, lr)
        metrics["lr"] = lr
        return new_params, new_opt, metrics

    return train_step


def _vjp_aux(fn, params):
    """jax.vjp for fn(params) -> (primal, aux): returns ((primal, aux),
    pullback_on_primal)."""
    primal, vjp_fn, aux = jax.vjp(fn, params, has_aux=True)

    def pull(ct):
        (g,) = vjp_fn(ct)
        return g

    return (primal, aux), pull


# ---------------------------------------------------------------------------
# TrainState-boundary cohort step (the repro.api engine contract)
# ---------------------------------------------------------------------------


def make_masked_cohort_step(model, opt_cfg, li: int,
                            grad_mode: str = "eq1") -> Callable:
    """:func:`make_cohort_train_step` gated by a traced participation mask:

        (client, copt, server, sopt, x, y, lr, lr_s, m)
            -> (client, copt, server, sopt, client_loss * m, server_loss * m)

    ``m`` is a 0/1 scalar (per lane under vmap).  The full step is always
    computed — fixed shapes, so churn in the active set never recompiles —
    and ``strategies.masked_update`` then keeps the old client/server
    params, Adam moments, and BN stats wherever ``m == 0`` (a zeroed loss
    alone would still advance Adam's moment decay).  With ``m == 1`` every
    output is bit-identical to the unmasked step (``x * 1.0 == x`` and the
    ``where`` selects the stepped value), which is what makes the
    population engines' participation-parity contract exact."""
    from repro.core.strategies import masked_update

    base = make_cohort_train_step(model, opt_cfg, li, grad_mode)

    def masked(client, copt, server, sopt, x, y, lr, lr_s, m):
        nc, nco, ns, nso, closs, sloss = base(client, copt, server, sopt,
                                              x, y, lr, lr_s)
        return (masked_update(m, nc, client), masked_update(m, nco, copt),
                masked_update(m, ns, server), masked_update(m, nso, sopt),
                closs * m, sloss * m)

    return masked


def make_cohort_train_step(model, opt_cfg, li: int,
                           grad_mode: str = "eq1") -> Callable:
    """One combined client+server step over the engine state-dict boundary:

        (client, copt, server, sopt, x, y, lr, lr_s)
            -> (client, copt, server, sopt, client_loss, server_loss)

    where ``client``/``server`` are ``{"trainable": ..., "state": ...}``
    dicts (the ``TrainState`` leaf layout, see repro/api/state.py), ``model``
    is a :class:`repro.api.protocol.SplitModel` adapter and ``li`` the
    cohort's cut layer.  Two gradient modes, mirroring the monolithic SPMD
    step above:

      * ``"eq1"`` — paper-faithful routing: the client family backprops its
        exit loss, the server family backprops the final loss, as two
        independent backward passes (exactly the composition the reference
        engine runs, so eq1 engines are cross-checkable to tolerance).
      * ``"sum"`` — one backward pass of the summed loss through the shared
        forward.  The split-boundary ``stop_gradient`` decouples the two
        parameter families, so the gradients are mathematically identical to
        eq1 — the mode trades the second VJP for one joint pass (recorded
        separately in benchmarks; convergence-tested, not bit-compared).

    Gradients never flow from server to client: ``h`` crosses the boundary
    through ``stop_gradient`` in both modes.  Both modes draw the per-side
    losses through ``strategies.client_loss_fn`` / ``server_loss_fn``, so
    adapter loss hooks (e.g. BackboneSplitModel's MoE load-balancing aux
    loss) reach every engine identically.
    """
    from repro.core.strategies import (client_loss_fn, make_client_step,
                                       make_server_step, server_loss_fn)

    if grad_mode == "eq1":
        cstep = make_client_step(model, opt_cfg)
        sstep = make_server_step(model, opt_cfg, li)

        def combined(client, copt, server, sopt, x, y, lr, lr_s):
            tr, st, copt, h, closs = cstep(client["trainable"],
                                           client["state"], copt, x, y, lr)
            h = jax.lax.stop_gradient(h)      # no server->client gradient
            srv, sst, sopt, sloss = sstep(server["trainable"],
                                          server["state"], sopt, h, y, lr_s)
            return ({"trainable": tr, "state": st}, copt,
                    {"trainable": srv, "state": sst}, sopt, closs, sloss)

        return combined

    if grad_mode != "sum":
        raise ValueError(f"unknown grad_mode {grad_mode!r}; expected "
                         f"'eq1' or 'sum'")

    closs_fn = client_loss_fn(model)
    sloss_fn = server_loss_fn(model, li)

    def joint_loss(ctr, strv, cst, sst, x, y):
        with jax.named_scope("client"):
            closs, (h, new_cst) = closs_fn(ctr, cst, x, y)
        h = jax.lax.stop_gradient(h)
        with jax.named_scope("server"):
            sloss, new_sst = sloss_fn(strv, sst, h, y)
        return closs + sloss, (closs, sloss, new_cst, new_sst)

    def combined(client, copt, server, sopt, x, y, lr, lr_s):
        (_, (closs, sloss, new_cst, new_sst)), (gc, gs) = jax.value_and_grad(
            joint_loss, argnums=(0, 1), has_aux=True)(
                client["trainable"], server["trainable"],
                client["state"], server["state"], x, y)
        tr, copt = adam_update(client["trainable"], gc, copt, opt_cfg, lr)
        srv, sopt = adam_update(server["trainable"], gs, sopt, opt_cfg, lr_s)
        return ({"trainable": tr, "state": new_cst}, copt,
                {"trainable": srv, "state": new_sst}, sopt, closs, sloss)

    return combined


# ---------------------------------------------------------------------------
# Sequential strategy at production scale (extension; Alg. 1 as SPMD)
# ---------------------------------------------------------------------------


def make_sequential_train_step(sc: StepConfig) -> Callable:
    """Alg. 1 fused into one jit program: a ``lax.scan`` over client groups.

    Each scan step processes ONE group's slice of the global batch: the
    client family (embed + layers <= l_g + exit head) updates from that
    group's exit loss, and the shared server side updates from the final
    loss with the paper's LR divisor (eta/N).  Deterministic order — the
    literal 'server processes features sequentially' semantics — while each
    per-group step still runs data/model-parallel on the mesh.

    Batch layout: group-contiguous (see ``boundary_ids_for_batch``); the
    batch must divide evenly by ``num_groups``.
    """
    cfg = sc.model
    nb = len(cfg.exit_layers)
    schedule = make_schedule(sc.train.optimizer)
    remat = sc.train.remat != "none"
    N = sc.splitee.profile.num_groups
    div = sc.splitee.resolved_server_lr_divisor()
    cs_cache: Dict[str, Any] = {}

    def group_loss(params, tokens, labels, split_ids):
        out = backbone_forward(params, cfg, tokens=tokens,
                               split_ids=split_ids, remat=remat)
        return hetero_losses(out, labels, split_ids, nb)

    def train_step(params, opt_state, batch):
        B = batch["tokens"].shape[0]
        per = B // N
        toks = batch["tokens"].reshape(N, per, -1)
        labs = batch["labels"].reshape(N, per, -1)
        sids = batch["split_ids"].reshape(N, per)
        lr = schedule(opt_state.step)

        cs, ss = participation_scale_trees(params, cfg, sc.splitee.profile)
        # sequential semantics: one group at a time; client-family grads at
        # full lr, server-family at lr / N (paper Table II).  One backward
        # pass cannot scale the two families separately on layers both reach,
        # so we blend by participation (exact on pure-client layers like the
        # embedding, scale 1, and pure-server layers like the head, 1/div).
        scale = jax.tree.map(
            lambda a, b: a * float(N) + b * float(N) / div, cs, ss)

        def body(carry, xs):
            p, o = carry
            t, l, s = xs

            def total(pp):
                c, srv, m = group_loss(pp, t, l, s)
                return c + srv, m

            (loss, m), g = jax.value_and_grad(total, has_aux=True)(p)
            g = jax.tree.map(lambda gg, sk: gg * sk, g, scale)
            p, o = adam_update(p, g, o, sc.train.optimizer, lr)
            return (p, o), m["server_loss"]

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), (toks, labs, sids))
        return params, opt_state, {"server_loss": jnp.mean(losses),
                                   "lr": lr}

    return train_step


# ---------------------------------------------------------------------------
# serve step factory (decode shapes; Alg. 3 gate fused in)
# ---------------------------------------------------------------------------


def make_serve_step(sc: StepConfig, boundary: int = 0) -> Callable:
    """One-token decode step with the entropy gate computed at the client
    boundary.  TPU SPMD computes both the exit and the full path and selects
    (docs/DESIGN.md §2); the request-routing savings are realized by the
    batching engine (``repro.api.serve_session.ServeSession``, which vmaps
    this step over its decode slots).

    ``boundary`` indexes ``sorted(cfg.exit_layers)`` — the order
    ``backbone_forward`` emits ``exit_logits`` in — so the gate head sits
    after cut layer ``sorted(cfg.exit_layers)[boundary]``.

    The returned ``serve_step`` accepts an optional runtime ``tau``
    (defaults to ``sc.splitee.entropy_threshold``); passing it as a traced
    scalar lets threshold sweeps (the paper's Fig. 2 axis) reuse one
    compilation."""
    cfg = sc.model
    tau_default = sc.splitee.entropy_threshold
    backend = dispatch.backend_for(cfg)

    def serve_step(params, tokens, cache, cache_len, embeds=None, enc=None,
                   tau=None):
        tau_ = tau_default if tau is None else tau
        out = backbone_forward(params, cfg, tokens=tokens, embeds=embeds,
                               enc=enc, cache=cache, cache_len=cache_len)
        if out.exit_logits:
            e_logits = out.exit_logits[boundary]
            # Alg. 3 gate on the cfg.kernels backend (pallas = the fused
            # streaming-entropy kernel; tau stays a traced scalar)
            H, exit_now = backend.entropy_gate(e_logits, tau_)  # (B, T)
            final = jnp.where(exit_now[..., None], e_logits, out.logits)
        else:
            H = softmax_entropy(out.logits)
            exit_now = jnp.zeros_like(H, bool)
            final = out.logits
        return {"logits": final, "exited": exit_now, "entropy": H,
                "cache": out.cache}

    return serve_step
