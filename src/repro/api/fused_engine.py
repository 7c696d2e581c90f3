"""Fused engine: scan + vmap whole-chunk execution as a pure
``TrainState -> TrainState`` executor (see docs/ENGINES.md).

  * **Cohorts + vmap** — clients sharing a split layer have identical pytree
    structure, so each cohort is stacked along a leading lane axis and its
    combined client+server step runs under one ``jax.vmap``.
  * **Rounds under lax.scan** — the exact minibatch sequence the reference
    engine would draw is pre-staged as ``[rounds, k, E, B, ...]`` device
    tensors and the whole chunk rolls through one ``jax.lax.scan`` with
    donated carry; losses come back as stacked per-round arrays (one host
    sync per chunk).
  * **In-graph Eq. (1)** — ``stacked_cross_layer_aggregate`` under a
    ``lax.cond`` on the traced ``(t+1) % aggregate_every == 0`` predicate.

Numerically equivalent to the reference engine in ``eq1`` grad mode (both
compose the same client/server step math through
``core.spmd.make_cohort_train_step``); enforced by
``tests/test_fused_engine.py`` and ``tests/test_session.py``.  The
Sequential strategy (Alg. 1) is inherently ordered across clients and is
not supported — ``resolve_engine("auto", ...)`` falls back to the
reference engine for it.

``repro.api.spmd_engine.SpmdEngine`` subclasses this engine and overrides
the :meth:`FusedEngine._compile_chunk` (jit with mesh shardings),
:meth:`FusedEngine._put_batch` / :meth:`FusedEngine._put_ts` (host
staging -> sharded, possibly process-global, device placement),
:meth:`FusedEngine._stack_carry` (recipe-sharded carry) and
:meth:`FusedEngine._fetch_carry` / :meth:`FusedEngine._host_losses`
(multi-host readback) hooks to stage the identical round body with mesh
shardings — including the overlapped staging pipeline, which calls the
same hooks from its producer thread.
"""
from __future__ import annotations

import collections
import itertools
import os
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.engines import (Engine, SessionContext, cohort_layout,
                               ragged_cohort_reason, register_engine)
from repro.api.state import TrainState
from repro.core.aggregation import (masked_stacked_cross_layer_aggregate,
                                    stacked_cross_layer_aggregate)
from repro.core.splitee import stack_pytrees, unstack_pytrees
from repro.core.spmd import make_cohort_train_step, make_masked_cohort_step
from repro.core.strategies import RoundMetrics
from repro.data.pipeline import effective_batch_size, prestage_batches
from repro.data.staging import StagedChunkPipeline


@register_engine("fused")
class FusedEngine(Engine):

    #: staging budget (bytes) for the auto ``chunk_rounds`` default: when a
    #: run's whole pre-staged ``[rounds, k, E, B, ...]`` tensor would exceed
    #: it, the run is split into budget-sized chunks instead of silently
    #: staging everything (full-size configs OOM before the first step
    #: otherwise).  The budget bounds *resident* staged data: under the
    #: overlapped pipeline it is divided by ``pipeline_depth`` so the
    #: staged-ahead chunks together still fit.  Override per instance, or
    #: via REPRO_STAGE_BUDGET_MB; must be strictly positive either way.
    stage_budget_bytes: int = 1 << 30

    #: overlapped staging: stage chunk n+1 on a background thread (a
    #: depth-2 double buffer, ``data.staging.StagedChunkPipeline``) while
    #: the jitted scan for chunk n runs, and fetch chunk n's losses only
    #: after chunk n+1 is dispatched.  Bit-identical trajectory either way
    #: (tests/test_staging.py); REPRO_OVERLAP_STAGING=0 is the kill switch.
    overlap_staging: bool = True

    #: staged chunks resident at once under the pipeline (2 = double
    #: buffer: one in compute, one staged ahead)
    pipeline_depth: int = 2

    #: with overlapped staging, a budget-sized single-chunk plan is
    #: subdivided into up to this many chunks so the double buffer has
    #: work to overlap (an explicit ``chunk_rounds`` is never subdivided;
    #: chunking is trajectory-neutral, see docs/ENGINES.md)
    pipeline_min_chunks: int = 4

    def __init__(self, ctx: SessionContext):
        super().__init__(ctx)
        self._cohort_lis, self._lanes = cohort_layout(
            ctx.profile.split_layers)
        self._counts: Dict[int, int] = {li: len(v)
                                        for li, v in self._lanes.items()}
        #: client index -> (cohort cut layer, lane position in the cohort)
        self._lane_pos: Dict[int, Tuple[int, int]] = {
            i: (li, j) for li in self._cohort_lis
            for j, i in enumerate(self._lanes[li])}
        self._chunk_fns: Dict[int, Callable] = {}
        #: the carry swap's two programs, one dispatch each per run (jit's
        #: own cache keys them on the state's tree structure)
        self._stack_fn = jax.jit(self._stack_lanes)
        self._unstack_fn = jax.jit(self._split_lanes)
        #: staging/overlap accounting for the most recent :meth:`run`
        #: (``data.staging.StageStats.as_dict`` — the bench's overlap leg
        #: reads it)
        self.last_stage_stats: Dict = {}
        #: per-round participation/straggler accounting for the most recent
        #: :meth:`run` of a population session ({} for fixed cohorts)
        self.last_participation_stats: Dict = {}
        # per-chunk [(num_active, num_stragglers), ...] handed from the
        # staging producer to the metrics consumer, FIFO in chunk order
        self._plan_stats: collections.deque = collections.deque()

    @classmethod
    def supports(cls, ctx: SessionContext):
        if ctx.strategy not in ("averaging", "distributed"):
            return (f"supports averaging/distributed only, not "
                    f"{ctx.strategy!r} (the Sequential strategy is ordered "
                    f"across clients — use the reference engine)")
        return ragged_cohort_reason(ctx)

    # -------------------------------------------------------------- tracing
    def _vstep(self, li: int) -> Callable:
        """One cohort step: the shared ``core.spmd.make_cohort_train_step``
        (eq1: exactly the reference engine's round body; sum: one fused
        backward of the summed loss), vmapped over lanes.  Population
        sessions use the mask-gated variant with a per-lane 0/1 mask as
        the extra vmapped argument."""
        if self.ctx.population is not None:
            masked = make_masked_cohort_step(self.ctx.model,
                                             self.ctx.opt_cfg,
                                             li, self.ctx.grad_mode)
            return jax.vmap(masked,
                            in_axes=(0, 0, 0, 0, 0, 0, None, None, 0))
        combined = make_cohort_train_step(self.ctx.model, self.ctx.opt_cfg,
                                          li, self.ctx.grad_mode)
        return jax.vmap(combined, in_axes=(0, 0, 0, 0, 0, 0, None, None))

    def _compile_chunk(self, chunk: Callable) -> Callable:
        """Stage the traced chunk.  The spmd subclass overrides this with
        mesh in/out shardings; here it is a plain donated jit."""
        return jax.jit(chunk, donate_argnums=(0,))

    def _chunk_fn(self, local_epochs: int) -> Callable:
        """Jitted ``(carry, ts, xs, ys[, ms]) -> (carry, (closs[n],
        sloss[n]))`` scanning the round body over a chunk; carry buffers
        are donated.  Population sessions scan an extra per-cohort
        ``[n, k]`` participation-mask input — a *traced* tensor, so a
        varying active set reuses one compilation (the jit cache-size
        proof in tests/test_population.py)."""
        if local_epochs in self._chunk_fns:
            return self._chunk_fns[local_epochs]

        ctx = self.ctx
        cohort_lis = self._cohort_lis
        counts = self._counts
        population = ctx.population is not None
        vsteps = {li: self._vstep(li) for li in cohort_lis}
        denom = float(ctx.N * local_epochs)
        averaging = ctx.strategy == "averaging"
        agg_every = ctx.cfg.aggregate_every
        schedule, lr_div = ctx.schedule, ctx.server_lr_div

        def epoch_body(carry, bx, by, ms, lr, lr_s):
            out, csum, ssum = {}, 0.0, 0.0
            for li in cohort_lis:
                client, copt, server, sopt = carry[li]
                args = (client, copt, server, sopt, bx[li], by[li], lr,
                        lr_s) + ((ms[li],) if population else ())
                client, copt, server, sopt, closs, sloss = vsteps[li](*args)
                out[li] = (client, copt, server, sopt)
                # population losses come back mask-weighted per lane, so
                # inactive slots contribute exact zeros to the sums
                csum = csum + jnp.sum(closs)
                ssum = ssum + jnp.sum(sloss)
            return out, (csum, ssum)

        def round_body(carry, inp):
            if population:
                t, xs, ys, ms = inp
            else:
                t, xs, ys = inp
                ms = None
            lr = schedule(t)
            lr_s = lr / lr_div

            def body(c, data):
                return epoch_body(c, data[0], data[1], ms, lr, lr_s)

            carry, (cs, ss) = jax.lax.scan(body, carry, (xs, ys))
            if population:
                # loss means over the round's ACTIVE set (masked clients
                # are excluded from the denominator; an all-masked round
                # reports 0 loss, guarded against 0/0)
                active = sum(jnp.sum(ms[li]) for li in cohort_lis)
                den = jnp.maximum(active, 1.0) * local_epochs
            else:
                den = denom
            if averaging:
                def aggregated(c):
                    agg = (
                        (lambda s: masked_stacked_cross_layer_aggregate(
                            s, ms, counts)) if population
                        else (lambda s: stacked_cross_layer_aggregate(
                            s, counts)))
                    tr = agg({li: c[li][2]["trainable"]
                              for li in cohort_lis})
                    st = agg({li: c[li][2]["state"] for li in cohort_lis})
                    return {li: (c[li][0], c[li][1],
                                 {"trainable": tr[li], "state": st[li]},
                                 c[li][3])
                            for li in cohort_lis}

                # cond (not where) so non-boundary rounds skip the Eq. (1)
                # means entirely — still in-graph, still no host sync
                do = ((t + 1) % agg_every) == 0
                carry = jax.lax.cond(do, aggregated, lambda c: c, carry)
            return carry, (jnp.sum(cs) / den, jnp.sum(ss) / den)

        if population:
            def chunk(carry, ts, xs, ys, ms):
                return jax.lax.scan(round_body, carry, (ts, xs, ys, ms))
        else:
            def chunk(carry, ts, xs, ys):
                return jax.lax.scan(round_body, carry, (ts, xs, ys))

        fn = self._compile_chunk(chunk)
        self._chunk_fns[local_epochs] = fn
        return fn

    # ------------------------------------------------------------- staging
    def _put_batch(self, arr: np.ndarray, li: int) -> jnp.ndarray:
        """Host-staged batch for cohort ``li`` -> device.  The spmd subclass
        overrides this to place each device's slice directly into the
        cohort's batch sharding."""
        return jnp.asarray(arr)

    def _stage_chunk(self, rounds: int, local_epochs: int):
        """Draw the chunk's minibatches through the session's data cursor
        (the same per-client sequence the reference engine would consume,
        in client-index order) straight into preallocated
        ``{li: [rounds, k, E, B, ...]}`` cohort buffers — one host copy
        per batch, no list/``np.stack``/lane-stack intermediates — then
        hand each buffer to :meth:`_put_batch`."""
        def drawn(i):
            while True:
                yield self.ctx.data.draw(i)

        bufs: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        with self.span("repro.stage.fill"):
            for i in range(self.ctx.N):
                li, j = self._lane_pos[i]
                it = drawn(i)
                first = next(it)          # fixes the staged shapes/dtypes
                if li not in bufs:
                    x0, y0 = first
                    k = self._counts[li]
                    bufs[li] = (
                        np.empty((rounds, local_epochs, k, *x0.shape),
                                 x0.dtype),
                        np.empty((rounds, local_epochs, k, *y0.shape),
                                 y0.dtype))
                bx, by = bufs[li]
                prestage_batches(itertools.chain([first], it), rounds,
                                 local_epochs, out=(bx[:, :, j], by[:, :, j]))
        with self.span("repro.stage.put"):
            xs = {li: self._put_batch(bufs[li][0], li)
                  for li in self._cohort_lis}
            ys = {li: self._put_batch(bufs[li][1], li)
                  for li in self._cohort_lis}
        return xs, ys

    def _put_mask(self, arr: np.ndarray, li: int) -> jnp.ndarray:
        """Host-staged ``[rounds, k]`` participation mask for cohort ``li``
        -> device.  The spmd subclass overrides this to shard the lane dim
        like the batch tensors."""
        return jnp.asarray(arr)

    def _stage_population_chunk(self, rounds: int, local_epochs: int):
        """Population staging: per absolute round, draw the participation
        plan and fill each *active* slot's lane from its assigned client's
        seeded iterator; inactive lanes stay zero-filled and masked out, so
        staged shapes are fixed regardless of churn.  Also records each
        round's (active, straggler) counts for the metrics consumer."""
        ctx = self.ctx
        bufs: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        masks: Dict[int, np.ndarray] = {}
        with self.span("repro.stage.fill"):
            for li in self._cohort_lis:
                x0, y0 = ctx.client_data[self._lanes[li][0]]
                k, B = self._counts[li], ctx.batch_size
                bufs[li] = (
                    np.zeros((rounds, local_epochs, k, B, *x0.shape[1:]),
                             x0.dtype),
                    np.zeros((rounds, local_epochs, k, B, *y0.shape[1:]),
                             y0.dtype))
                masks[li] = np.zeros((rounds, k), np.float32)
            stats = []
            for r in range(rounds):
                plan, slot_batches = ctx.pop_cursor.next_round(local_epochs)
                stats.append((plan.num_active, plan.num_stragglers))
                for e, drawn in slot_batches.items():
                    li, j = self._lane_pos[e]
                    masks[li][r, j] = 1.0
                    bx, by = bufs[li]
                    for ei, (x, y) in enumerate(drawn):
                        bx[r, ei, j] = x
                        by[r, ei, j] = y
        self._plan_stats.append(stats)
        with self.span("repro.stage.put"):
            xs = {li: self._put_batch(bufs[li][0], li)
                  for li in self._cohort_lis}
            ys = {li: self._put_batch(bufs[li][1], li)
                  for li in self._cohort_lis}
            ms = {li: self._put_mask(masks[li], li)
                  for li in self._cohort_lis}
        return xs, ys, ms

    def _round_stage_bytes(self, local_epochs: int) -> int:
        """Host bytes one round of pre-staged batches occupies (every
        client's ``local_epochs`` minibatches, x and y)."""
        total = 0
        for x, y in self.ctx.client_data:
            eb = effective_batch_size(len(x), self.ctx.batch_size)
            per_example = (x.dtype.itemsize * int(np.prod(x.shape[1:]))
                           + y.dtype.itemsize * int(np.prod(y.shape[1:])))
            total += local_epochs * eb * per_example
        return total

    def _auto_chunk_rounds(self, rounds: int, local_epochs: int,
                           overlap: bool = False) -> int:
        """The default chunk size when the caller passed ``chunk_rounds=0``:
        as many rounds as fit the staging budget (at least one).  With
        ``overlap`` the pipeline keeps up to ``pipeline_depth`` staged
        chunks resident at once (one in compute plus staged-ahead), so the
        budget is divided by the depth — resident staged data stays within
        ``stage_budget_bytes`` instead of depth times it.  An
        explicit per-instance ``stage_budget_bytes`` wins over the
        REPRO_STAGE_BUDGET_MB environment default.  Either knob must be
        strictly positive — a zero/negative budget used to silently
        degrade to ``chunk_rounds=1``, hiding the misconfiguration."""
        budget = self.stage_budget_bytes
        env = os.environ.get("REPRO_STAGE_BUDGET_MB")
        if env and budget == FusedEngine.stage_budget_bytes:
            try:
                budget = int(env) << 20
            except ValueError:
                raise ValueError(
                    f"REPRO_STAGE_BUDGET_MB={env!r} is not an integer "
                    f"megabyte count") from None
            if budget <= 0:
                raise ValueError(
                    f"REPRO_STAGE_BUDGET_MB={env} must be strictly "
                    f"positive: a 0/negative staging budget cannot hold "
                    f"even one round of pre-staged batches")
        if budget <= 0:
            raise ValueError(
                f"stage_budget_bytes={budget} must be strictly positive: "
                f"a 0/negative staging budget cannot hold even one round "
                f"of pre-staged batches (set FusedEngine.stage_budget_bytes "
                f"or REPRO_STAGE_BUDGET_MB to a real byte/MB count)")
        if overlap:
            budget //= self.pipeline_depth
        per_round = max(1, self._round_stage_bytes(local_epochs))
        return max(1, min(rounds, budget // per_round))

    def _overlap_enabled(self) -> bool:
        """The ``overlap_staging`` knob, with REPRO_OVERLAP_STAGING (0 /
        false / off disables, anything else enables) taking precedence."""
        env = os.environ.get("REPRO_OVERLAP_STAGING")
        if env is not None:
            return env.strip().lower() not in ("0", "false", "off", "no")
        return self.overlap_staging

    def _chunk_plan(self, rounds: int, chunk_rounds: int,
                    local_epochs: int, overlap: bool) -> List[int]:
        """The run's chunk sizes in execution order.  An explicit
        ``chunk_rounds`` is honored exactly; the auto default is the
        staging-budget chunk (budget divided by ``pipeline_depth`` under
        overlap), subdivided (equal-ish, for compile-cache
        reuse) into up to ``pipeline_min_chunks`` pieces when overlap is
        on and the budget would cover the run in one chunk — a pipeline
        with a single chunk has nothing to overlap.  Chunk boundaries
        never change the trajectory (docs/ENGINES.md, tested)."""
        chunk = (chunk_rounds if chunk_rounds > 0
                 else self._auto_chunk_rounds(rounds, local_epochs, overlap))
        if (chunk_rounds <= 0 and overlap and chunk >= rounds
                and rounds >= 2):
            pieces = min(self.pipeline_min_chunks, rounds)
            chunk = -(-rounds // pieces)                   # ceil
        plan = []
        done = 0
        while done < rounds:
            n = min(chunk, rounds - done)
            plan.append(n)
            done += n
        return plan

    def _stack_lanes(self, clients, copts, servers, sopts):
        """Per-client states -> the ``{li: (client, copt, server, sopt)}``
        cohort carry, each leaf stacked along a leading lane axis.  Traced
        once into the stack program, so the adapter's ``stack_clients``
        keeps its say over how clients and servers stack."""
        model = self.ctx.model
        carry = {}
        for li in self._cohort_lis:
            lanes = self._lanes[li]
            carry[li] = (
                model.stack_clients([clients[i] for i in lanes]),
                stack_pytrees([copts[i] for i in lanes]),
                model.stack_clients([servers[i] for i in lanes]),
                stack_pytrees([sopts[i] for i in lanes]),
            )
        return carry

    def _split_lanes(self, carry):
        """The cohort carry -> ``{li: (clients, copts, servers, sopts)}``,
        each a list of the cohort's per-lane trees."""
        return {li: tuple(unstack_pytrees(t, self._counts[li])
                          for t in carry[li])
                for li in self._cohort_lis}

    def _stack_carry(self, clients, copts, servers, sopts):
        """Stack the run's carry in one compiled dispatch, not one per leaf
        group (a ResNet-18 Table-IV carry has about 500).  Inputs are not
        donated: the caller's ``TrainState`` still owns them."""
        return self._stack_fn(clients, copts, servers, sopts)

    def _unstack_carry(self, carry, clients, copts, servers, sopts):
        """Split the run-final carry back into per-client states.  A device
        carry is sliced in one compiled dispatch, not three device ops per
        leaf; a host carry (the multi-process spmd fetch) is sliced on the
        host, where numpy views cost nothing and stay host arrays."""
        on_device = all(isinstance(x, jax.Array)
                        for x in jax.tree.leaves(carry))
        parts = (self._unstack_fn if on_device else self._split_lanes)(carry)
        for li in self._cohort_lis:
            cs, co, ss, so = parts[li]
            for j, i in enumerate(self._lanes[li]):
                clients[i], copts[i] = cs[j], co[j]
                servers[i], sopts[i] = ss[j], so[j]

    def _fetch_carry(self, carry):
        """Hook: the run's final device carry, host-readable.  Identity
        here (single-process arrays are always addressable); the spmd
        engine reshards to replicated + fetches when the carry spans
        processes."""
        return carry

    def _put_ts(self, t: int, n: int):
        """Hook: the chunk's round-index vector ``[t, t+n)`` as a device
        array.  The spmd engine overrides this to build a process-global
        replicated array under multi-host runs."""
        return jnp.arange(t, t + n, dtype=jnp.int32)

    def _host_losses(self, closs, sloss):
        """Hook: a chunk's stacked per-round losses as host arrays (the
        one blocking sync per chunk).  The spmd engine overrides this to
        read a local shard of the replicated outputs under multi-host."""
        return np.asarray(closs), np.asarray(sloss)

    def _chunk_metrics(self, t0: int, n: int, closs, sloss,
                       log_every: int) -> List[RoundMetrics]:
        with self.span("repro.fused.loss_sync"):             # one sync/chunk
            closs, sloss = self._host_losses(closs, sloss)
        # population: the staging producer queued this chunk's per-round
        # (active, straggler) counts before the chunk became consumable
        stats = self._plan_stats.popleft() if self._plan_stats else None
        metrics = []
        for r in range(n):
            a, s = stats[r] if stats is not None else (-1, 0)
            m = RoundMetrics(t0 + r, float(closs[r]), float(sloss[r]),
                             active_clients=a, stragglers=s)
            metrics.append(m)
            if log_every and (m.round % log_every == 0):
                extra = (f"  active {a}/{self.ctx.N}  stragglers {s}"
                         if stats is not None else "")
                print(f"round {m.round:4d}  client_loss {m.client_loss:.4f}"
                      f"  server_loss {m.server_loss:.4f}{extra}")
        return metrics

    # ------------------------------------------------------------ training
    def _run(self, state: TrainState, rounds: int, local_epochs: int = 1,
             log_every: int = 0, chunk_rounds: int = 0
             ) -> Tuple[TrainState, List[RoundMetrics]]:
        """``chunk_rounds`` bounds how many rounds of pre-staged data are
        resident at once (0 = auto: budget-sized chunks, subdivided for the
        staging pipeline — chunking never changes the trajectory, see
        docs/ENGINES.md).

        Chunks execute as a producer/consumer pipeline: the carry is
        stacked and placed once per run and stays device-resident across
        chunks; a background producer stages chunk n+1 (draw + fill +
        ``device_put``) while the jitted scan for chunk n runs, and the
        host sync on chunk n's losses happens only after chunk n+1 is
        dispatched — JAX dispatch is async, so the old per-chunk
        ``np.asarray`` used to serialize staging against compute."""
        if rounds <= 0:
            return state, []
        population = self.ctx.population is not None
        if population:
            # the population cursor is round-addressed: the seeded schedule
            # replays rounds [0, t0) on restore/rewind
            self.ctx.pop_cursor.align(int(state.round), local_epochs)
        else:
            self.ctx.data.align(state.batches_drawn)
        overlap = self._overlap_enabled()
        plan = self._chunk_plan(rounds, chunk_rounds, local_epochs, overlap)
        fn = self._chunk_fn(local_epochs)
        clients, copts = list(state.clients), list(state.client_opts)
        servers, sopts = list(state.servers), list(state.server_opts)
        with self.span("repro.fused.stack_carry"):
            carry = self._stack_carry(clients, copts, servers, sopts)
        t0 = int(state.round)

        stage = (self._stage_population_chunk if population
                 else self._stage_chunk)
        self._plan_stats.clear()
        pipeline = StagedChunkPipeline(
            lambda n: stage(n, local_epochs), plan,
            depth=self.pipeline_depth, overlap=overlap, phases=self.phases)
        metrics: List[RoundMetrics] = []
        pending = None                  # (chunk start round, n, closs, sloss)
        try:
            t = t0
            for n in plan:
                staged = pipeline.get()
                # async dispatch: this chunk's scan starts on device while
                # the producer stages the next chunk ...
                with self.span("repro.fused.dispatch"):
                    ts = self._put_ts(t, n)
                    carry, (closs, sloss) = fn(carry, ts, *staged)
                # ... and only then does the host block on the PREVIOUS
                # chunk's losses (syncing this chunk's would serialize the
                # whole loop again)
                if pending is not None:
                    metrics.extend(self._chunk_metrics(*pending, log_every))
                    pipeline.release()
                pending = (t, n, closs, sloss)
                t += n
            metrics.extend(self._chunk_metrics(*pending, log_every))
            pipeline.release()
        finally:
            pipeline.close()
            self.last_stage_stats = pipeline.stats.as_dict()
        if population:
            actives = [m.active_clients for m in metrics]
            strags = [m.stragglers for m in metrics]
            self.last_participation_stats = {
                "rounds": len(metrics),
                "slots": self.ctx.N,
                "population": self.ctx.population.num_clients,
                "active_total": int(sum(actives)),
                "active_mean": (float(np.mean(actives)) if actives
                                else 0.0),
                "straggler_total": int(sum(strags)),
                "masked_total": int(len(metrics) * self.ctx.N
                                    - sum(actives)),
                "active_per_round": actives,
                "stragglers_per_round": strags,
            }

        with self.span("repro.fused.unstack_carry"):
            self._unstack_carry(self._fetch_carry(carry), clients, copts,
                                servers, sopts)
        new_state = state.replace(
            clients=tuple(clients), client_opts=tuple(copts),
            servers=tuple(servers), server_opts=tuple(sopts),
            round=jnp.asarray(t0 + rounds, jnp.int32),
            batches_drawn=state.batches_drawn
            + jnp.asarray(rounds * local_epochs, jnp.int32))
        return new_state, metrics
