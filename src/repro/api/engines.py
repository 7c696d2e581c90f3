"""Engine registry and the engine execution contract.

An *engine* is an execution backend for the paper's cooperative strategies:
a pure ``TrainState -> TrainState`` executor.  Engines never own training
state — they receive a state, run some rounds, and return the new state plus
per-round metrics, so states can be checkpointed, resumed, and handed
between engines freely (the resume-equivalence contract in docs/API.md).

Registered engines:

  * ``"reference"`` — per-client jitted loop, the paper-faithful oracle;
    supports every strategy including Sequential (Alg. 1).
  * ``"fused"``     — scan+vmap whole-chunk execution for Averaging /
    distributed (docs/ENGINES.md).
  * ``"spmd"``      — the fused round body staged under jit with mesh
    shardings: the global batch shards over the mesh's batch axes
    (``repro.api.spmd_engine``, built on the core/spmd.py cohort step).
    Needs a mesh (``TrainSession(..., mesh=...)``) or >1 visible device.

``resolve_engine("auto", ctx)`` picks the widest valid engine for the
session's strategy, data layout, and device topology (spmd on a mesh,
fused on one device, reference otherwise) instead of failing at runtime,
and reports *why* candidates were skipped (surfaced by
``TrainSession.engine_name`` so benchmark manifests record the real
execution path); naming an engine explicitly validates it at construction
and raises with the precise reason if it cannot run.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple, Type

import numpy as np

from repro import obs
from repro.config import OptimizerConfig, SplitEEConfig
from repro.data.pipeline import batch_iterator, effective_batch_size
from repro.optim import make_schedule


# ---------------------------------------------------------------------------
# Session context: everything static an engine needs (model, configs, data).
# Host-side and immutable apart from the iterator cache, which is keyed by
# the state's ``batches_drawn`` cursor so engines stay pure w.r.t. state.
# ---------------------------------------------------------------------------


class DataCursor:
    """Seeded per-client batch streams addressed by draw count.

    ``align(cursor)`` positions every client's ``batch_iterator`` at the
    given number of already-drawn batches — reusing the live iterators when
    the cursor matches (the common run-after-run case) and otherwise
    rebuilding from the seed and replaying, which reproduces the exact
    upcoming batch (and augmentation RNG) sequence after a checkpoint
    restore or a state rewind."""

    def __init__(self, client_data: Sequence[Tuple[np.ndarray, np.ndarray]],
                 batch_size: int, seed: int, augment=None):
        self.client_data = client_data
        self.batch_size = batch_size
        self.seed = seed
        self.augment = augment
        self._iters: Optional[list] = None
        self._pos: Optional[Tuple[int, ...]] = None

    def align(self, cursor) -> None:
        want = tuple(int(c) for c in np.asarray(cursor))
        if self._pos == want:
            return
        self._iters = [
            batch_iterator(x, y, self.batch_size, seed=self.seed + i,
                           augment=self.augment)
            for i, (x, y) in enumerate(self.client_data)]
        for it, k in zip(self._iters, want):
            for _ in range(k):
                next(it)
        self._pos = want

    def draw(self, i: int) -> Tuple[np.ndarray, np.ndarray]:
        assert self._iters is not None, "align() before draw()"
        batch = next(self._iters[i])
        pos = list(self._pos)
        pos[i] += 1
        self._pos = tuple(pos)
        return batch


class SessionContext:
    """Static bundle shared by a session and its engine: the model adapter,
    configs, derived schedule/LR constants, and the data cursor."""

    def __init__(self, model, splitee_cfg: SplitEEConfig,
                 opt_cfg: OptimizerConfig,
                 client_data: Optional[Sequence[Tuple[np.ndarray,
                                                      np.ndarray]]],
                 batch_size: int, *, augment=None, seed: int = 0,
                 mesh=None, grad_mode: str = "eq1", recipe=None,
                 population=None):
        if grad_mode not in ("eq1", "sum"):
            raise ValueError(f"unknown grad_mode {grad_mode!r}; expected "
                             f"'eq1' or 'sum'")
        # resolve eagerly so a bad --recipe name dies at the facade, not
        # inside an engine; the spmd engine reads the resolved dataclass
        from repro.launch.shardings import recipe_name, resolve_recipe
        self.recipe = resolve_recipe(recipe)
        self.recipe_name = recipe_name(recipe)
        self.model = model
        self.cfg = splitee_cfg
        self.opt_cfg = opt_cfg
        self.batch_size = batch_size
        self.augment = augment
        self.seed = seed
        self.mesh = mesh
        self.grad_mode = grad_mode
        self.population = population

        self.profile = splitee_cfg.profile
        self.strategy = splitee_cfg.strategy
        self.N = self.profile.num_groups
        if population is not None:
            # the population drives the data plane: its slot layout must BE
            # the profile's client groups, and the per-slot "shards" the
            # buffer/sharding-sizing code reads are placeholder views
            if client_data is not None:
                raise ValueError(
                    "pass either client_data or population, not both — a "
                    "population session draws every staged batch from the "
                    "population's per-client shards")
            if augment is not None:
                raise ValueError(
                    "augment is not supported with a client population "
                    "yet; bake augmentation into the population shards")
            population.validate_for(self.profile.split_layers, batch_size)
            client_data = population.slot_stubs()
        elif client_data is None:
            raise ValueError("client_data is required without a population")
        self.client_data = client_data
        if len(client_data) != self.N:
            raise ValueError(f"profile has {self.N} client groups but "
                             f"{len(client_data)} data shards were given")
        self.schedule = make_schedule(opt_cfg)
        self.server_lr_div = splitee_cfg.resolved_server_lr_divisor()
        self.data = DataCursor(client_data, batch_size, seed, augment)
        if population is not None:
            from repro.population import PopulationCursor
            self.pop_cursor = PopulationCursor(population, batch_size, seed)
        else:
            self.pop_cursor = None


# ---------------------------------------------------------------------------
# Engine base + registry
# ---------------------------------------------------------------------------


class Engine:
    """Base class: a pure ``state -> state`` executor bound to a context.

    Instances may cache compiled functions (jitted steps, scan chunks) —
    caches are derived from the immutable context, never from state."""

    name: str = "?"

    def __init__(self, ctx: SessionContext):
        reason = self.supports(ctx)
        if reason:
            raise ValueError(reason)
        self.ctx = ctx
        #: the current run's program spans (``repro.obs``)
        self.phases = obs.Phases()
        #: host seconds and count of each program span in the most recent
        #: :meth:`run`: ``{name: {"s": seconds, "n": count}}``
        self.last_run_stats: Dict[str, Dict[str, float]] = {}

    @classmethod
    def supports(cls, ctx: SessionContext) -> Optional[str]:
        """``None`` if this engine can run the session, else a human-readable
        reason (used both for auto-selection and for construction errors)."""
        return None

    def run(self, state, rounds: int, local_epochs: int = 1,
            log_every: int = 0, chunk_rounds: int = 0):
        """Train ``rounds`` rounds from ``state``; returns
        ``(new_state, [RoundMetrics])``.  Must not mutate ``state``.  The
        run is the ``repro.train`` span; :attr:`last_run_stats` holds its
        spans when it returns or raises."""
        self.phases = obs.Phases()
        try:
            with self.span("repro.train"):
                return self._run(state, rounds, local_epochs, log_every,
                                 chunk_rounds)
        finally:
            self.last_run_stats = self.phases.as_dict()

    def _run(self, state, rounds: int, local_epochs: int, log_every: int,
             chunk_rounds: int):
        """The engine's own :meth:`run`."""
        raise NotImplementedError

    def span(self, name: str) -> obs.span:
        """A program span of the current run."""
        return obs.span(name, self.phases)


_REGISTRY: Dict[str, Type[Engine]] = {}

#: auto-selection preference: widest engine first (spmd wants a mesh or >1
#: device; fused wants averaging/distributed; reference takes everything)
AUTO_ORDER = ("spmd", "fused", "reference")


def register_engine(name: str) -> Callable[[Type[Engine]], Type[Engine]]:
    def deco(cls: Type[Engine]) -> Type[Engine]:
        cls.name = name
        _REGISTRY[name] = cls
        return cls
    return deco


def get_engine(name: str) -> Type[Engine]:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown engine {name!r}; registered engines: "
                         f"{available_engines()}") from None


def available_engines() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def resolve_engine(name: str, ctx: SessionContext
                   ) -> Tuple[Type[Engine], Optional[str]]:
    """Resolve an engine name (or ``"auto"``) against a session context.

    Returns ``(engine_cls, selection_note)``.  ``"auto"`` picks the first
    engine in :data:`AUTO_ORDER` whose ``supports`` accepts the context —
    e.g. a single-device averaging session falls back from spmd to fused,
    and Sequential-strategy sessions fall back to the reference engine
    instead of raising the way an explicit ``engine="fused"`` request does.
    When auto-selection skipped wider candidates, ``selection_note`` says
    why (e.g. ``"spmd unavailable: ... only 1 device visible"``) so the
    real execution path is auditable (``TrainSession.engine_name``);
    explicit requests resolve with ``selection_note=None`` or raise."""
    if name == "auto":
        skipped: List[Tuple[List[str], str]] = []
        for cand in AUTO_ORDER:
            cls = _REGISTRY[cand]
            reason = cls.supports(ctx)
            if reason is None:
                # engines sharing a reason (e.g. spmd+fused on Sequential)
                # collapse into one entry so the note stays readable
                note = "; ".join(f"{'/'.join(names)} unavailable: {r}"
                                 for names, r in skipped) or None
                return cls, note
            if skipped and skipped[-1][1] == reason:
                skipped[-1][0].append(cand)
            else:
                skipped.append(([cand], reason))
        raise ValueError("no registered engine supports this session ("
                         + "; ".join(f"{'/'.join(names)}: {r}"
                                     for names, r in skipped) + ")")
    cls = get_engine(name)
    reason = cls.supports(ctx)
    if reason:
        raise ValueError(reason)
    return cls, None


def cohort_layout(split_layers: Sequence[int]
                  ) -> Tuple[Tuple[int, ...], Dict[int, List[int]]]:
    """Group client indices into cohorts by split layer: returns the sorted
    distinct cut layers and ``{li: [client indices]}``."""
    lis = tuple(sorted(set(split_layers)))
    lanes = {li: [i for i, l in enumerate(split_layers) if l == li]
             for li in lis}
    return lis, lanes


def ragged_cohort_reason(ctx: SessionContext) -> Optional[str]:
    """Cohort lanes are stacked into one ``[k, B, ...]`` tensor, so clients
    sharing a cut layer must emit equal effective batch sizes; return the
    offending cohort's description if not (the reference engine has no such
    constraint)."""
    _, lanes = cohort_layout(ctx.profile.split_layers)
    for li, members in lanes.items():
        bs = {i: effective_batch_size(len(ctx.client_data[i][0]),
                                      ctx.batch_size)
              for i in members}
        if len(set(bs.values())) > 1:
            return (f"cohort l_i={li} mixes effective batch sizes {bs} "
                    f"(batch_size={ctx.batch_size} clamped to shard "
                    f"length); equalize client shards or use the "
                    f"reference engine")
    return None
