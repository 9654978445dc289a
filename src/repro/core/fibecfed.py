"""FibecFed — Algorithm 1, end to end, on real (host-simulated) FL clients.

Initialization phase (Lines 1-10):
  * per-device Fisher difficulty score per batch (Formulas 16-17), ascending
    sort (curriculum order);
  * per-device layer sensitivity scores (Eq. 9-10) → server aggregation
    (Eq. 11) → GAL selection with the lossless count (or configured fraction);
  * per-device momentum-FIM warmup → neuron masks for local update (§4.3.2).

Tuning phase (Lines 11-19): sample K devices, merge global GAL params into
each client's LoRA, curriculum-select batches, run masked local SGD/AdamW,
FedAvg the GAL part on the server.

Three interchangeable round engines (``engine=``):

* ``"vectorized"`` (default) — clients' LoRA/opt-state/mask pytrees are
  stacked along a leading client axis and the whole round runs as one jitted
  device program (``repro.core.engine``): ``lax.scan`` over curriculum steps
  inside a ``vmap`` over clients, with the weighted GAL FedAvg fused in and
  buffer donation. The init phase likewise scores all (client, batch) cells
  in one call and batches the FIM warmup.
* ``"sharded"`` — the vectorized programs with the stacked client axis
  sharded over a device mesh (``mesh=``, default a data-only mesh over every
  device): each device trains its shard of the chosen cohort and the fused
  weighted GAL FedAvg becomes an all-reduce over the client axis. The client
  stack and the per-round cohort are padded up to multiples of the mesh's
  client-group count with inert rows (zero weight / zero valid steps), so
  numerics stay bit-compatible with ``"vectorized"``.
* ``"loop"`` — the legacy reference path: one jitted call per (client, batch)
  step, host-side merge and FedAvg. Kept for equivalence testing
  (``tests/test_engine_equivalence.py``) and as the semantic spec.
* ``"async"`` — straggler-aware event-driven aggregation
  (``repro.federated.async_agg``): an event queue on a virtual clock models
  per-client compute/comm latency under a heterogeneity ``scenario=``
  (``repro.federated.hetero`` presets — speed skew, dropout, bursty
  arrival), each client trains its own jitted scan program
  (``engine.build_client_train_fn``, no vmap barrier), and the server
  merges any ``buffer_size`` completions into a double-buffered global with
  staleness-discounted FedAvg weights. ``async_cfg=AsyncAggConfig(...)``
  layers the adaptive policies on top: FedAsync-style delta merges with a
  server learning rate (``merge_mode="delta"``), a staleness cutoff,
  completion-rate-adaptive buffer size, per-client step-count adaptation,
  and wall-clock-aware cohort sampling. With the homogeneous scenario,
  buffer = cohort size, and the policies at their defaults it reduces
  exactly to the synchronous engines; comm bytes are attributed per
  completion event.

Baseline/ablation switches (used by benchmarks, mirroring the paper's
comparisons): ``difficulty_metric`` (fisher | loss | length | random),
``curriculum`` strategies, ``gal_mode`` (importance | full | random |
ascending | descending), ``sparse_update`` on/off.
"""
from __future__ import annotations

import dataclasses
import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import FibecFedConfig
from repro.core import curriculum as curr
from repro.core import engine as eng
from repro.core import fisher as fish
from repro.core import gal as galmod
from repro.core import sparse as sparsemod
from repro.core.curriculum import CurriculumSchedule
from repro.data.pipeline import (
    bucket_size,
    gather_batch,
    make_batches,
    stack_clients,
    stack_cohort,
)
from repro.kernels import ops as kops
from repro.lora import (
    gal_mask_tree,
    lora_num_logical_layers,
    neuron_mask_tree,
    rank_mask_tree,
)
from repro.models.model_api import ModelFns
from repro.obs import ensure as ensure_telemetry
from repro.obs import runtime_metrics
from repro.optim import make_optimizer
from repro.train.losses import make_logits_loss

ENGINES = ("vectorized", "loop", "sharded", "async")

# Compiled programs shared across FibecFed instances. Runners built on the
# same model/loss_fn objects (every baseline preset in a comparison, both
# engines in an equivalence check) would otherwise re-jit identical programs
# per instance — compile time dwarfs run time at test/benchmark scale. Keys
# are (kind, loss_fn/probe_fn, hyperparams...); function objects hash by
# identity, so distinct models never collide.
_PROGRAM_MEMO: Dict[tuple, Any] = {}


def _memo(key, build):
    if key not in _PROGRAM_MEMO:
        # a memo miss is a fresh program build (trace + compile on first
        # call) — the process-wide compile counter observability hangs off
        # this single choke point
        runtime_metrics.counter("jit.program_builds").inc()
        _PROGRAM_MEMO[key] = build()
    return _PROGRAM_MEMO[key]


def _compile_within(fn, args):
    """``fn`` compiled for ``args``, or None where the compiler finds that
    it does not fit the device's memory (it refuses such a program)."""
    try:
        return fn.lower(*args).compile()
    except jax.errors.JaxRuntimeError as e:
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        return None


def _program_bytes(compiled, in_use: int = 0) -> int:
    """Device bytes a compiled program needs: its arguments, outputs and
    temporaries (aliased outputs once), plus ``in_use`` bytes the device
    holds besides the arguments. 0 where the backend gives no analysis."""
    m = compiled.memory_analysis()
    if m is None:
        return 0
    args = m.argument_size_in_bytes
    own = args + m.output_size_in_bytes + m.temp_size_in_bytes - m.alias_size_in_bytes
    return own + max(0, in_use - args)


@contextmanager
def _round_phase(tel, name: str) -> Iterator[None]:
    """A host phase of a stacked-engine round: the span ``round_<name>`` on
    track ``server`` (an annotation alone with telemetry off), and its
    seconds in the runtime histogram ``fl.round_<name>_s`` either way."""
    start = time.perf_counter()
    with tel.span(f"round_{name}", cat="fl", track="server"):
        yield
    runtime_metrics.histogram(f"fl.round_{name}_s").observe(time.perf_counter() - start)


def clear_compile_caches() -> None:
    """Drop all memoized programs (and cached loss functions).

    The memo intentionally pins loss functions, models, and XLA executables
    for the process lifetime; a long-lived sweep over many models can call
    this between models to bound resident memory. This covers every engine's
    programs — including the async engine's per-client train programs
    (``"client_train"`` keys), the standalone merge programs (``"gal_merge"``
    and the delta-mode ``"gal_delta_merge"``/``"lora_delta"``), whose donated
    client buffers must never outlive a cache clear (see
    ``tests/test_async_agg.py``'s re-init regression test).
    """
    from repro.train import losses as _losses

    runtime_metrics.counter("jit.cache_clears").inc()
    _PROGRAM_MEMO.clear()
    _losses._LOSS_FN_CACHE.clear()


@dataclasses.dataclass
class ClientState:
    data: Dict[str, np.ndarray]
    n: int
    batches: List[np.ndarray]
    order: np.ndarray  # curriculum order over batches
    opt_state: Any
    fim: Any = None  # momentum diag-FIM
    neuron_mask: Any = None  # update-mask tree (or None = dense)
    difficulty: Optional[np.ndarray] = None
    layer_scores: Optional[np.ndarray] = None
    lossless_fraction: float = 1.0
    # compression error-feedback residual (loop/async engines; the stacked
    # engines keep one stacked residual tree on the runner instead)
    ef_residual: Any = None
    # Either a concrete LoRA tree (loop engine) or a zero-cost view into the
    # vectorized engine's stacked tree, materialized only on access so the
    # round hot path never pays for per-client host bookkeeping.
    _lora: Any = None
    _lora_view: Optional[Callable[[], Any]] = None

    @property
    def lora(self) -> Any:
        if self._lora_view is not None:
            return self._lora_view()
        return self._lora

    @lora.setter
    def lora(self, value: Any) -> None:
        self._lora = value
        self._lora_view = None


class FibecFed:
    def __init__(
        self,
        model: ModelFns,
        loss_fn: Callable,
        fl: FibecFedConfig,
        client_data: Sequence[Dict[str, np.ndarray]],
        *,
        optimizer: str = "sgd",
        fused_optimizer: bool = False,
        difficulty_metric: str = "fisher",
        gal_mode: str = "importance",
        sparse_update: bool = True,
        engine: str = "vectorized",
        mesh: Optional[Any] = None,
        scenario: Optional[Any] = None,
        async_cfg: Optional[Any] = None,
        compression: Optional[Any] = None,
        client_ranks: Optional[Sequence[int]] = None,
        store: Optional[Any] = None,
        hierarchy: Optional[Any] = None,
        telemetry: Optional[Any] = None,
        seed: int = 0,
    ):
        """Build an FL runner over host-simulated clients.

        Args:
          model: the ``ModelFns`` bundle from ``repro.models.build_model``
            (init/forward/probe closures over one architecture config).
          loss_fn: ``loss_fn(params, lora, batch) -> scalar`` from
            ``repro.train.make_loss_fn(model)``; its ``.masked`` variant (if
            present) powers the padded-batch fast paths.
          fl: the ``FibecFedConfig`` hyperparameters (cohort size, rounds,
            curriculum ``beta``/``alpha``, GAL fraction, sparse ratio, ...).
          client_data: one dict of equal-length arrays per client (the
            non-IID shards; ``repro.data.dirichlet_partition`` makes them).
          optimizer: local optimizer name, ``"sgd"`` or ``"adamw"``.
          fused_optimizer: ``True`` routes local updates through the fused
            Pallas masked-update kernels (one read/write pass per leaf);
            ``"force"`` pins the kernel path even for sub-tile leaves.
          difficulty_metric: curriculum difficulty — ``"fisher"`` (paper),
            ``"loss"``, ``"length"``, or ``"random"`` (ablations).
          gal_mode: GAL layer selection — ``"importance"`` (paper),
            ``"full"``, ``"random"``, ``"ascending"``, ``"descending"``.
          sparse_update: apply the momentum-FIM neuron keep-masks to local
            updates (paper §4.3.2); ``False`` trains dense LoRA.
          engine: round execution strategy — one of ``ENGINES``
            (``"vectorized"`` default; see the class docstring).
          mesh: device mesh for ``engine="sharded"`` (default: a data-only
            mesh over every XLA device); rejected for other engines.
          scenario: device-heterogeneity preset (name or
            ``repro.federated.hetero.ScenarioPreset``) for
            ``engine="async"``; rejected for sync engines.
          async_cfg: ``repro.federated.async_agg.AsyncAggConfig`` — buffer
            size/concurrency/staleness discount plus the adaptive knobs
            (``merge_mode``/``server_lr``, ``staleness_cutoff``,
            ``adapt_buffer``, ``adapt_steps``, ``sampling_bias``); only
            meaningful with ``engine="async"``.
          compression: ``repro.federated.CompressionConfig`` — fake-quantize
            the client→server GAL delta (int8/int4/top-k, with per-client
            error-feedback residuals) and charge the compressed payload in
            comm accounting. ``None`` / ``mode="none"`` is an exact no-op:
            every engine takes the untouched PR 5 code paths. May also be
            set via ``async_cfg.compression`` (they must agree if both set).
          client_ranks: per-client effective LoRA rank (resource-adaptive):
            client ``i`` trains only the first ``client_ranks[i]`` rank
            components — the rest stay frozen at the pulled values, so its
            delta is exactly zero there and rank-heterogeneous aggregation
            is plain masked FedAvg into the full server rank. Pull/push
            bytes are rank-projected. Defaults to full rank everywhere;
            under ``engine="async"`` a scenario with
            ``slow_rank_fraction < 1`` derives ranks for the slow group.
          store: a ``repro.federated.store.ClientStore`` owning the client
            states. ``None`` (default) binds an ``InMemoryStore`` — the
            whole population resident, bit-identical to the pre-store
            engines. An ``OutOfCoreStore`` keeps only an LRU hot set of
            client states resident (cold clients spill to flat-npz), so
            peak memory is bounded by the hot-set size, not the population;
            the stacked round then runs over just the sampled cohort
            (``engine="vectorized"``) or the dispatched client
            (``engine="async"``). Rejected for ``engine="sharded"`` — the
            mesh-sharded population stack is resident by construction.
          hierarchy: two-tier edge→server aggregation topology for
            ``engine="async"`` (an int edge count or
            ``repro.federated.hierarchy.HierarchyConfig``): each edge
            reduces its region's buffered payloads to one partial weighted
            sum and the server merges the edge summaries with unit weights
            — bit-exact to the flat merge at one edge, equal up to float
            reassociation otherwise. ``None`` (default) merges flat.
          telemetry: an optional ``repro.obs.Telemetry`` — spans every
            round/init phase on the wall clock (and, under ``engine="async"``,
            every client completion on the virtual clock), and fills the
            metrics registry (rounds/sec, per-round loss, comm bytes,
            staleness, buffer occupancy). ``None`` (the default) installs the
            no-op recorder: the run is bit-identical to an uninstrumented
            one (CI-enforced).
          seed: seeds client sampling, GAL randomness, and params/LoRA init;
            the async scenario stream derives from it at a fixed offset so
            heterogeneity never perturbs cohort-sampling equivalence.
        """
        if engine not in ENGINES:
            raise ValueError(f"engine must be one of {ENGINES}, got {engine!r}")
        if engine == "sharded":
            from repro.launch.mesh import make_client_mesh

            mesh = mesh if mesh is not None else make_client_mesh()
        elif mesh is not None:
            raise ValueError("mesh= is only meaningful with engine='sharded'")
        if engine != "async" and (scenario is not None or async_cfg is not None):
            raise ValueError(
                "scenario=/async_cfg= are only meaningful with engine='async'"
            )
        # lazy imports: repro.federated's package init imports this module
        from repro.federated.hierarchy import get_hierarchy
        from repro.federated.store import ClientsView, InMemoryStore

        if store is None:
            store = InMemoryStore()
        if store.out_of_core and engine == "sharded":
            raise ValueError(
                "engine='sharded' keeps the mesh-sharded population stack "
                "resident by construction; use an in-memory store"
            )
        self.store = store
        self._oocore = bool(store.out_of_core)
        if hierarchy is not None and engine != "async":
            raise ValueError("hierarchy= is only meaningful with engine='async'")
        self._hierarchy = None if hierarchy is None else get_hierarchy(hierarchy)
        self.mesh = mesh
        self.model = model
        self.cfg = model.cfg
        self.loss_fn = loss_fn
        self.fl = fl
        self.difficulty_metric = difficulty_metric
        self.gal_mode = gal_mode
        self.sparse_update = sparse_update
        self.engine = engine
        self.tel = ensure_telemetry(telemetry)
        self.rng = np.random.default_rng(seed)
        self.key = jax.random.PRNGKey(seed)
        self._seed = seed

        self.params = model.init_params(jax.random.fold_in(self.key, 0))
        init_lora = model.init_lora(jax.random.fold_in(self.key, 1))
        # private copy: global_lora's buffers are donated by the vectorized
        # round program, and mask building needs live arrays afterwards
        self._init_lora = jax.tree.map(jnp.copy, init_lora)
        self.global_lora = init_lora  # server copy (GAL part authoritative)

        # fused_optimizer=True routes local updates through the fused Pallas
        # masked-update kernels (repro.kernels.masked_update) — same frozen-
        # moment semantics, one read/write pass per leaf; "force" pins the
        # kernel path even for sub-tile leaves (kernel-coverage tests). The
        # flag is part of every optimizer-program memo key: fused and unfused
        # updates trace different programs.
        self.optimizer_name = optimizer
        self.fused_optimizer = fused_optimizer
        self._opt_key = (optimizer, fused_optimizer)
        self.opt_init, self.opt_update = make_optimizer(optimizer, fused=fused_optimizer)

        self.schedule = CurriculumSchedule(
            strategy=fl.curriculum,
            beta=fl.beta_initial_ratio,
            alpha=fl.alpha_full_data,
            total_rounds=fl.rounds,
        )

        vectorized = engine in ("vectorized", "sharded")
        self._stacked_engine = vectorized
        self._async = engine == "async"
        if self._async:
            from repro.federated.async_agg import AsyncAggConfig, DoubleBufferedGlobal
            from repro.federated.hetero import get_scenario

            self.scenario = get_scenario(scenario)
            self.async_cfg = async_cfg if async_cfg is not None else AsyncAggConfig()
            self._global = DoubleBufferedGlobal(self.global_lora)
            self._scheduler = None  # built lazily on the first async round

        # --- compressed uploads + resource-adaptive per-client rank ---
        # lazy import: repro.federated's package init imports this module
        from repro.federated.compress import CompressionConfig

        if self._async and self.async_cfg.compression is not None:
            if compression is not None and compression != self.async_cfg.compression:
                raise ValueError(
                    "compression= conflicts with async_cfg.compression; set one"
                )
            compression = self.async_cfg.compression
        if compression is not None and not isinstance(compression, CompressionConfig):
            raise TypeError(
                f"compression must be a CompressionConfig, got {type(compression)!r}"
            )
        # mode="none" normalizes to None so defaults take the PR 5 code paths
        self.compression = (
            compression if compression is not None and compression.enabled else None
        )

        if client_ranks is None and self._async and self.scenario.slow_rank_fraction < 1.0:
            from repro.federated.hetero import SCENARIO_SEED_OFFSET

            bound = self.scenario.bind(
                len(client_data), seed=seed + SCENARIO_SEED_OFFSET
            )
            client_ranks = bound.client_ranks(self.cfg.lora_rank)
        if client_ranks is not None:
            ranks = np.asarray(client_ranks, np.int64)
            if ranks.shape != (len(client_data),):
                raise ValueError("client_ranks needs exactly one rank per client")
            if np.any(ranks < 1) or np.any(ranks > self.cfg.lora_rank):
                raise ValueError(
                    f"client_ranks must lie in [1, {self.cfg.lora_rank}]"
                )
            if np.all(ranks == self.cfg.lora_rank):
                ranks = None  # exact no-op: take the untouched code paths
            self.client_ranks = ranks
        else:
            self.client_ranks = None
        self._rank_mask_cache: Dict[int, Any] = {}
        self._comp_mask_cache: Dict[int, Any] = {}

        oocore = self._oocore

        def _make_state(ci: int) -> ClientState:
            cd = client_data[ci]
            n = len(next(iter(cd.values())))
            return ClientState(
                data=cd,
                n=n,
                batches=make_batches(n, fl.batch_size),
                order=np.arange(max(1, (n + fl.batch_size - 1) // fl.batch_size)),
                # in-memory stacked engines keep client state in stacked
                # trees and clients get lazy views (below); everyone else —
                # loop, async, and every out-of-core engine — owns concrete
                # per-client LoRA/opt copies
                _lora=(
                    None
                    if vectorized and not oocore
                    else jax.tree.map(jnp.copy, init_lora)
                ),
                opt_state=(
                    None if vectorized and not oocore else self.opt_init(init_lora)
                ),
            )

        def _make_shell(ci: int) -> ClientState:
            # re-fetch scaffold for a spilled client: the store overwrites
            # the host metadata from its resident copy and the device fields
            # from the client's npz
            cd = client_data[ci]
            n = len(next(iter(cd.values())))
            return ClientState(
                data=cd,
                n=n,
                batches=make_batches(n, fl.batch_size),
                order=np.arange(max(1, (n + fl.batch_size - 1) // fl.batch_size)),
                opt_state=None,
            )

        self.store.bind(
            client_data=client_data,
            make_state=_make_state,
            make_shell=_make_shell,
            telemetry=self.tel,
        )
        self.clients: Sequence[ClientState] = ClientsView(self.store)

        if self._async and not oocore:
            # per-client concrete LoRA/opt state (like the loop engine), but
            # data on the padded fixed-shape grid: every client's (NB, B, ...)
            # row has the same shape, so one compiled per-client scan program
            # (per step-count bucket) serves the whole population
            stack = stack_clients(client_data, fl.batch_size)
            self._stack_data = {k_: jnp.asarray(v) for k_, v in stack.data.items()}
            self._sample_valid = jnp.asarray(stack.sample_valid)

        if vectorized and not oocore:
            C = len(self.clients)
            k = min(fl.devices_per_round, C)
            if self.mesh is not None:
                # pad the stack to a multiple of the mesh's client groups,
                # with enough inert rows to also pad each round's cohort
                from repro.launch.mesh import num_client_groups

                G = num_client_groups(self.mesh)
                self._cohort_pad = -(-k // G) * G
                C_stack = -(-(C + self._cohort_pad - k) // G) * G
            else:
                self._cohort_pad = k
                C_stack = C
            stack = stack_clients(client_data, fl.batch_size, pad_clients_to=C_stack)
            self._stack_data = {k_: jnp.asarray(v) for k_, v in stack.data.items()}
            self._sample_valid = jnp.asarray(stack.sample_valid)
            self._stacked_lora = jax.tree.map(
                lambda x: jnp.repeat(x[None], C_stack, axis=0), init_lora
            )
            opt0 = self.opt_init(init_lora)
            self._stacked_opt = jax.tree.map(
                lambda x: jnp.repeat(jnp.asarray(x)[None], C_stack, axis=0), opt0
            )
            self._stacked_mask = None  # built in init_phase when sparse_update
            # compression state (built in init_phase when enabled): stacked
            # per-client error-feedback residuals + top-k count masks
            self._stacked_residual = None
            self._stacked_comp_mask = None
            if self.mesh is not None:
                client_shd = eng.client_sharding(self.mesh)
                repl_shd = eng.replicated_sharding(self.mesh)
                self._stack_data = jax.device_put(self._stack_data, client_shd)
                self._sample_valid = jax.device_put(self._sample_valid, client_shd)
                self._stacked_lora = jax.device_put(self._stacked_lora, client_shd)
                self._stacked_opt = jax.device_put(self._stacked_opt, client_shd)
                self.params = jax.device_put(self.params, repl_shd)
                self.global_lora = jax.device_put(self.global_lora, repl_shd)
            for ci, client in enumerate(self.clients):
                client._lora_view = (
                    lambda ci=ci: jax.tree.map(lambda x: x[ci], self._stacked_lora)
                )

        self.gal_layers: Optional[np.ndarray] = None  # bool (L_logical,)
        self._gal_mask_tree = None
        self._gal_leaf_cache: Optional[List[tuple]] = None
        self._comm_bytes_cache: Dict[Optional[int], tuple] = {}

        # bytes accounting (paper §5.6): LoRA params up+down per round, wire
        # dtype per leaf; the upload-only series isolates the compressed
        # push (the pull is always raw, so total ratios saturate near 2x)
        self.comm_bytes_per_round: List[int] = []
        self.comm_upload_bytes_per_round: List[int] = []
        # sync engines record (chosen, client_steps) per round so benchmarks
        # can price the round barrier under a hetero.ScenarioPreset
        self.last_round_info: Optional[Dict[str, np.ndarray]] = None
        # packed-round lane counts by scan length: _lane_count
        self._lane_counts: Dict[int, int] = {}
        # the init programs' memory budget (None: the device's) and the
        # chunk of clients each ran with (_run_init_program)
        self._init_bytes_limit: Optional[int] = None
        self.init_chunks: Dict[str, Optional[int]] = {}

    # ------------------------------------------------------------------
    # stacked client state (ownership lives on the store)
    # ------------------------------------------------------------------
    # The vectorized/sharded engines' population-stacked trees belong to the
    # in-memory store (they ARE client state); these shims keep the runner's
    # historical attribute names working for engines, tests, and benchmarks.
    # On stores without stacked state (out-of-core) the getters read None.

    @property
    def _stacked_lora(self):
        return getattr(self.store, "stacked_lora", None)

    @_stacked_lora.setter
    def _stacked_lora(self, value):
        self.store.stacked_lora = value

    @property
    def _stacked_opt(self):
        return getattr(self.store, "stacked_opt", None)

    @_stacked_opt.setter
    def _stacked_opt(self, value):
        self.store.stacked_opt = value

    @property
    def _stacked_mask(self):
        return getattr(self.store, "stacked_mask", None)

    @_stacked_mask.setter
    def _stacked_mask(self, value):
        self.store.stacked_mask = value

    @property
    def _stacked_residual(self):
        return getattr(self.store, "stacked_residual", None)

    @_stacked_residual.setter
    def _stacked_residual(self, value):
        self.store.stacked_residual = value

    @property
    def _stacked_comp_mask(self):
        return getattr(self.store, "stacked_comp_mask", None)

    @_stacked_comp_mask.setter
    def _stacked_comp_mask(self, value):
        self.store.stacked_comp_mask = value

    # ------------------------------------------------------------------
    # jitted primitives (loop engine + shared)
    # ------------------------------------------------------------------

    def _grad_step(self):
        loss_fn, opt_update = self.loss_fn, self.opt_update

        def build():
            def step(params, lora, opt_state, batch, lr, mask):
                loss, grads = jax.value_and_grad(
                    lambda lo: loss_fn(params, lo, batch)
                )(lora)
                new_lora, new_opt = opt_update(grads, opt_state, lora, lr, mask)
                return loss, new_lora, new_opt

            return jax.jit(step)

        return _memo(("grad_step", loss_fn, self._opt_key), build)

    def _sample_scores(self):
        loss_fn = self.loss_fn
        return _memo(
            ("sample_scores", loss_fn),
            lambda: jax.jit(
                lambda params, lora, batch: fish.per_sample_fisher_scores(
                    loss_fn, params, lora, batch
                )
            ),
        )

    def _fim_diag(self):
        loss_fn = self.loss_fn
        return _memo(
            ("fim_diag", loss_fn),
            lambda: jax.jit(
                lambda params, lora, batch: fish.fim_diag(loss_fn, params, lora, batch)
            ),
        )

    def _batch_loss(self):
        return _memo(("batch_loss", self.loss_fn), lambda: jax.jit(self.loss_fn))

    def _sensitivity_fn(self):
        """Jitted layer-sensitivity probe (Eq. 9-10); shared by both engines."""
        cfg, fl, probe = self.cfg, self.fl, self.model.forward_probe
        logits_loss = make_logits_loss(cfg)

        def build():
            @jax.named_scope("sensitivity_probe")
            def fn(params, lora, batch):
                B, T = batch["tokens"].shape
                S = T + (cfg.num_prefix_embeddings if cfg.family == "vlm" else 0)
                return galmod.layer_sensitivity_scores(
                    probe,
                    logits_loss,
                    params,
                    lora,
                    batch,
                    gamma=fl.noise_budget,
                    p=fl.norm_p,
                    noise_shape=(B, S, cfg.d_model),
                )

            return jax.jit(fn)

        return _memo(("sensitivity", probe, fl.noise_budget, fl.norm_p), build)

    # vectorized-engine programs -----------------------------------------

    def _difficulty_fn(self, chunk: Optional[int] = None):
        loss_fn, metric, mesh = self.loss_fn, self.difficulty_metric, self.mesh
        tail = () if chunk is None else (chunk,)
        if mesh is not None:
            return _memo(
                ("difficulty", loss_fn, metric, mesh) + tail,
                lambda: eng.build_sharded_difficulty_fn(loss_fn, metric, mesh, chunk),
            )
        return _memo(
            ("difficulty", loss_fn, metric) + tail,
            lambda: eng.build_difficulty_fn(loss_fn, metric, chunk),
        )

    def _fim_warmup_fn(self, chunk: Optional[int] = None):
        loss_fn, momentum, mesh = self.loss_fn, self.fl.fim_momentum, self.mesh
        tail = () if chunk is None else (chunk,)
        if mesh is not None:
            return _memo(
                ("fim_warmup", loss_fn, momentum, mesh) + tail,
                lambda: eng.build_sharded_fim_warmup_fn(loss_fn, momentum, mesh, chunk),
            )
        return _memo(
            ("fim_warmup", loss_fn, momentum) + tail,
            lambda: eng.build_fim_warmup_fn(loss_fn, momentum, chunk),
        )

    def _init_memory(self) -> Optional[tuple]:
        """``(budget, bytes in use)`` of the init programs' device. The
        budget is ``_init_bytes_limit`` where set (tests force chunking
        through it), else ``memory_stats()["bytes_limit"]``; None where the
        backend states no memory and no budget is set."""
        dev = self.mesh.devices.flat[0] if self.mesh is not None else jax.devices()[0]
        stats = dev.memory_stats() or {}
        limit = self._init_bytes_limit or stats.get("bytes_limit")
        return None if limit is None else (limit, stats.get("bytes_in_use", 0))

    def _run_init_program(self, kind: str, build: Callable, args: tuple):
        """The init-phase program ``kind`` (``build(chunk)``:
        :meth:`_difficulty_fn` or :meth:`_fim_warmup_fn`) over every client,
        in chunks of clients where all at once would not fit the device.

        Where the device states its memory, the all-clients program is
        compiled first; while the compiler refuses it for memory, or its
        ``memory_analysis()`` (arguments, outputs and temporaries, plus what
        else the device holds) exceeds the budget, it is rebuilt over half
        as many clients at a time. The executable that fits is kept per
        program and shapes, so later calls compile nothing, and it is the
        one called. Where the device states no memory, the program is the
        jitted all-clients one. The chunk taken is in ``init_chunks[kind]``
        (None: all at once)."""
        full, memory = build(None), self._init_memory()
        if memory is None:
            return full(*args)
        limit, in_use = memory
        shapes = tuple((tuple(x.shape), str(x.dtype)) for x in jax.tree.leaves(args))
        key = ("init_fit", full, shapes, limit)
        if key not in _PROGRAM_MEMO:
            clients = jax.tree.leaves(args[1])[0].shape[0]
            chunk, exe = None, _compile_within(full, args)
            while (exe is None or _program_bytes(exe, in_use) > limit) and (chunk or clients) > 1:
                chunk = max(1, (chunk or clients) // 2)
                exe = _compile_within(build(chunk), args)
            if exe is None:  # one client at a time does not fit either: say so
                exe = build(chunk).lower(*args).compile()
            _PROGRAM_MEMO[key] = (exe, chunk)
        exe, chunk = _PROGRAM_MEMO[key]
        self.init_chunks[kind] = chunk
        return exe(*args)

    def _compress_static(self) -> Optional[Dict[str, Any]]:
        """Static compression spec baked into the round program (trace-time
        constants: quantizer width, top-k fraction, which optional inputs
        exist). ``None`` when compression is off — the untouched builders
        produce bit-identical programs to the uncompressed stack."""
        if self.compression is None:
            return None
        c = self.compression
        return {
            "qmax": c.qmax,
            "topk_ratio": c.topk_ratio,
            "use_thresh": c.use_thresh,
            "error_feedback": c.error_feedback,
            "has_comp_mask": bool(c.use_thresh and self.client_ranks is not None),
        }

    def _round_fn(self):
        loss_fn, opt_update, mesh = self.loss_fn, self.opt_update, self.mesh
        use_mask = self._stacked_mask is not None
        comp = self._compress_static()
        if comp is not None:
            ckey = tuple(sorted(comp.items()))
            if mesh is not None:
                return _memo(
                    ("round_c", loss_fn, self._opt_key, use_mask, ckey, mesh),
                    lambda: eng.build_sharded_compressed_round_fn(
                        loss_fn, opt_update, use_neuron_mask=use_mask,
                        compress=comp, mesh=mesh,
                    ),
                )
            return _memo(
                ("round_c", loss_fn, self._opt_key, use_mask, ckey),
                lambda: eng.build_compressed_round_fn(
                    loss_fn, opt_update, use_neuron_mask=use_mask, compress=comp
                ),
            )
        if mesh is not None:
            return _memo(
                ("round", loss_fn, self._opt_key, use_mask, mesh),
                lambda: eng.build_sharded_round_fn(
                    loss_fn, opt_update, use_neuron_mask=use_mask, mesh=mesh
                ),
            )
        return _memo(
            ("round", loss_fn, self._opt_key, use_mask),
            lambda: eng.build_round_fn(loss_fn, opt_update, use_neuron_mask=use_mask),
        )

    def _packed_round_fn(self):
        """The single-device round program over a :func:`curr.pack_lanes`
        plan (:func:`eng.build_packed_round_fn`), with or without
        compression; :meth:`_round_fn` stays the unpacked program."""
        loss_fn, opt_update = self.loss_fn, self.opt_update
        use_mask = self._stacked_mask is not None
        comp = self._compress_static()
        ckey = None if comp is None else tuple(sorted(comp.items()))
        return _memo(
            ("round_packed", loss_fn, self._opt_key, use_mask, ckey),
            lambda: eng.build_packed_round_fn(
                loss_fn, opt_update, use_neuron_mask=use_mask, compress=comp
            ),
        )

    def _lane_count(self, S: int) -> int:
        """Lanes of a packed round at scan length ``S``
        (:func:`curr.lane_count` over the whole population and schedule):
        the same for every round and cohort, so one packed program per step
        bucket."""
        if S not in self._lane_counts:
            k = min(self.fl.devices_per_round, len(self.clients))
            self._lane_counts[S] = curr.lane_count(
                self.schedule, [len(c.order) for c in self.clients], k, S,
                self.fl.local_epochs,
            )
        return self._lane_counts[S]

    def _lane_plan(self, batch_idx, step_valid):
        """The cohort's :func:`curr.pack_lanes` plan at the bucket's lane
        count, or ``None`` for the unpacked program: where the lane count is
        no less than the cohort (balanced shards), or the cohort does not
        fit."""
        k, S = batch_idx.shape
        L = self._lane_count(S)
        if L >= k:
            return None
        return curr.pack_lanes([bi[sv > 0] for bi, sv in zip(batch_idx, step_valid)], S, L)

    def _cohort_round_fn(self, use_mask: bool):
        """Round program over a *materialized cohort* (out-of-core store):
        the stacked engines' round body minus the population gather/scatter
        bookends. Programs are keyed on the cohort-stack shape by ``jit``;
        ``stack_cohort``'s pow2 batch bucketing keeps the distinct shapes
        (and therefore compiles) logarithmic in the population's spread."""
        loss_fn, opt_update = self.loss_fn, self.opt_update
        comp = self._compress_static()
        if comp is not None:
            ckey = tuple(sorted(comp.items()))
            return _memo(
                ("cohort_round_c", loss_fn, self._opt_key, use_mask, ckey),
                lambda: eng.build_cohort_compressed_round_fn(
                    loss_fn, opt_update, use_neuron_mask=use_mask, compress=comp
                ),
            )
        return _memo(
            ("cohort_round", loss_fn, self._opt_key, use_mask),
            lambda: eng.build_cohort_round_fn(
                loss_fn, opt_update, use_neuron_mask=use_mask
            ),
        )

    # async-engine programs ----------------------------------------------

    def _client_train_fn(self):
        """Per-client jitted local round (async engine): scan over the
        client's curriculum steps with no vmap barrier. Memoized like every
        other program so ``clear_compile_caches`` covers it."""
        loss_fn, opt_update = self.loss_fn, self.opt_update
        # presence-based: rank keep-masks fold into neuron_mask even with
        # sparse_update off, and they must gate local updates identically
        use_mask = self.clients[0].neuron_mask is not None
        return _memo(
            ("client_train", loss_fn, self._opt_key, use_mask),
            lambda: eng.build_client_train_fn(
                loss_fn, opt_update, use_neuron_mask=use_mask
            ),
        )

    def _merge_fn(self):
        """Standalone fused GAL merge (async buffer flush)."""
        return _memo(("gal_merge",), eng.build_merge_fn)

    def _delta_merge_fn(self):
        """FedAsync-style delta application (async ``merge_mode="delta"``)."""
        return _memo(("gal_delta_merge",), eng.build_delta_merge_fn)

    def _delta_fn(self):
        """Client delta extraction (trained LoRA minus pulled global)."""
        return _memo(("lora_delta",), eng.build_delta_fn)

    # ------------------------------------------------------------------
    # initialization phase (Alg. 1 lines 1-10)
    # ------------------------------------------------------------------

    def _client_batch(self, client: ClientState, batch_ids: np.ndarray):
        return gather_batch(client.data, batch_ids)

    def _host_batch_difficulty(self, client: ClientState) -> np.ndarray:
        """length/random difficulty metrics — host-only, shared by engines
        (identical RNG consumption order keeps the engines equivalent)."""
        metric = self.difficulty_metric
        scores = np.zeros(len(client.batches))
        for j, ids in enumerate(client.batches):
            if metric == "length":  # Shortformer/SLW-style static heuristic
                scores[j] = float(np.sum(client.data["tokens"][ids] != 0))
            elif metric == "random":
                scores[j] = self.rng.random()
            else:
                raise ValueError(metric)
        return scores

    def _batch_difficulty(self, client: ClientState) -> np.ndarray:
        metric = self.difficulty_metric
        if metric in ("length", "random"):
            return self._host_batch_difficulty(client)
        scores = np.zeros(len(client.batches))
        for j, ids in enumerate(client.batches):
            batch = self._client_batch(client, ids)
            if metric == "fisher":
                s = self._sample_scores()(self.params, client.lora, batch)
                scores[j] = float(jnp.sum(s))  # Formula 17
            elif metric == "loss":  # SE/inference-loss heuristic baseline
                scores[j] = float(self._batch_loss()(self.params, client.lora, batch))
            else:
                raise ValueError(metric)
        return scores

    def _compute_difficulty(self) -> None:
        """Lines 2-5: per-batch difficulty + ascending curriculum order."""
        metric = self.difficulty_metric
        if self._stacked_engine and not self._oocore and metric in ("fisher", "loss"):
            # one program over every (client, batch) cell, each client scored
            # with its own LoRA (matters on re-init after training rounds)
            scores = self._run_init_program(
                "difficulty", self._difficulty_fn,
                (self.params, self._stacked_lora, self._stack_data, self._sample_valid),
            )
            with self.tel.span("difficulty_read", cat="fl", track="server"):
                scores = np.asarray(scores)
            for ci, client in enumerate(self.clients):
                client.difficulty = scores[ci, : len(client.batches)]
                client.order = curr.order_batches(
                    client.difficulty, self.schedule.strategy
                )
            return
        for client in self.clients:
            client.difficulty = self._batch_difficulty(client)
            client.order = curr.order_batches(client.difficulty, self.schedule.strategy)

    def _select_local_masks(self) -> None:
        """Lines 8-10: momentum-FIM warmup → per-client neuron keep-masks."""
        fl, span = self.fl, self.tel.span
        if self._stacked_engine and not self._oocore:
            C = len(self.clients)
            C_stack = self._sample_valid.shape[0]  # includes mesh padding rows
            with span("fim_gather", cat="fl", track="server"):
                warm_idx = np.zeros((C_stack, fl.fim_warmup_epochs), np.int64)
                for ci, c in enumerate(self.clients):
                    warm_idx[ci] = [
                        int(c.order[min(e, len(c.order) - 1)])
                        for e in range(fl.fim_warmup_epochs)
                    ]
                rows = jnp.arange(C_stack)[:, None]
                cols = jnp.asarray(warm_idx)
                wdata = {k: v[rows, cols] for k, v in self._stack_data.items()}
                wsv = self._sample_valid[rows, cols]
                if self.mesh is not None:
                    # the eager gather above leaves committed replicated arrays;
                    # the sharded warmup program wants them client-sharded
                    client_shd = eng.client_sharding(self.mesh)
                    wdata = jax.device_put(wdata, client_shd)
                    wsv = jax.device_put(wsv, client_shd)
            with span("fim_program", cat="fl", track="server"):
                fims = self._run_init_program(
                    "fim_warmup", self._fim_warmup_fn, (self.params, self._stacked_lora, wdata, wsv)
                )
            with span("fim_select", cat="fl", track="server"):
                importance = sparsemod.neuron_importance(fims)  # leaves (C, L, d_out)
                if fl.sparse_ratio is not None:
                    keep = sparsemod.select_neuron_masks(importance, fl.sparse_ratio)
                    self._stacked_mask = jax.vmap(
                        lambda kp: neuron_mask_tree(self.cfg, self._init_lora, kp)
                    )(keep)
                else:  # per-client lossless ρ: build masks client by client
                    per_client = []
                    for ci, client in enumerate(self.clients):
                        imp_ci = jax.tree.map(lambda x: x[ci], importance)
                        keep = sparsemod.select_neuron_masks(
                            imp_ci, client.lossless_fraction
                        )
                        per_client.append(
                            neuron_mask_tree(self.cfg, self._init_lora, keep)
                        )
                    # padding rows are never trained; any finite mask will do
                    per_client += [per_client[0]] * (C_stack - C)
                    self._stacked_mask = jax.tree.map(
                        lambda *xs: jnp.stack(xs), *per_client
                    )
                if self.mesh is not None:
                    self._stacked_mask = jax.device_put(
                        self._stacked_mask, eng.client_sharding(self.mesh)
                    )
            with span("fim_slice", cat="fl", track="server"):
                for ci, client in enumerate(self.clients):
                    client.fim = jax.tree.map(lambda x: x[ci], fims)
                    client.neuron_mask = jax.tree.map(
                        lambda x: x[ci], self._stacked_mask
                    )
            return
        for ci, client in enumerate(self.clients):
            fim = None
            for e in range(fl.fim_warmup_epochs):
                ids = client.batches[int(client.order[min(e, len(client.order) - 1)])]
                batch = self._client_batch(client, ids)
                new = self._fim_diag()(self.params, client.lora, batch)
                fim = fish.fim_momentum_update(fim, new, fl.fim_momentum)
            client.fim = fim
            importance = sparsemod.neuron_importance(fim)
            rho = (
                fl.sparse_ratio
                if fl.sparse_ratio is not None
                else client.lossless_fraction
            )
            keep = sparsemod.select_neuron_masks(importance, rho)
            client.neuron_mask = neuron_mask_tree(self.cfg, client.lora, keep)

    def _rank_mask(self, rank: int) -> Any:
        if rank not in self._rank_mask_cache:
            self._rank_mask_cache[rank] = rank_mask_tree(self._init_lora, rank)
        return self._rank_mask_cache[rank]

    def _comp_mask(self, ci: int) -> Any:
        """Top-k count mask for client ``ci``: GAL support × rank keep-mask
        (the fraction is taken of the values the client can actually send).
        Cached per distinct rank — the trees are rank-, not client-, shaped.
        """
        rank = int(self.client_ranks[ci])
        if rank not in self._comp_mask_cache:
            self._comp_mask_cache[rank] = jax.tree.map(
                lambda m, r: m * r, self._gal_mask_tree, self._rank_mask(rank)
            )
        return self._comp_mask_cache[rank]

    def _fold_rank_masks(self) -> None:
        """Fold per-client rank keep-masks into the update masks.

        A rank-``r_i`` client's beyond-rank LoRA components stay frozen at
        the pulled values, so its delta there is exactly zero and the
        existing masked FedAvg aggregates rank-heterogeneous updates into
        the full server rank with no pad/project pass. Idempotent (binary
        masks), so repeated ``init_phase`` calls are safe.
        """
        per_client = [self._rank_mask(int(r)) for r in self.client_ranks]
        if self._stacked_engine and not self._oocore:
            C_stack = self._sample_valid.shape[0]
            padded = per_client + [per_client[0]] * (C_stack - len(per_client))
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *padded)
            self._stacked_mask = (
                stacked
                if self._stacked_mask is None
                else jax.tree.map(jnp.multiply, self._stacked_mask, stacked)
            )
            if self.mesh is not None:
                self._stacked_mask = jax.device_put(
                    self._stacked_mask, eng.client_sharding(self.mesh)
                )
            for ci, client in enumerate(self.clients):
                client.neuron_mask = jax.tree.map(
                    lambda x: x[ci], self._stacked_mask
                )
            return
        for ci, client in enumerate(self.clients):
            rm = per_client[ci]
            client.neuron_mask = (
                rm
                if client.neuron_mask is None
                else jax.tree.map(jnp.multiply, client.neuron_mask, rm)
            )

    def _reset_compression_state(self) -> None:
        """Zero the error-feedback residuals and (re)build the stacked
        top-k count masks. Called from ``init_phase``: the GAL support the
        residuals live on may have changed."""
        if self.compression is None:
            return
        if self._stacked_engine and not self._oocore:
            if self.compression.error_feedback:
                self._stacked_residual = jax.tree.map(
                    jnp.zeros_like, self._stacked_lora
                )
                if self.mesh is not None:
                    self._stacked_residual = jax.device_put(
                        self._stacked_residual, eng.client_sharding(self.mesh)
                    )
            if self.compression.use_thresh and self.client_ranks is not None:
                C_stack = self._sample_valid.shape[0]
                per = [self._comp_mask(ci) for ci in range(len(self.clients))]
                per += [per[0]] * (C_stack - len(per))
                self._stacked_comp_mask = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *per
                )
                if self.mesh is not None:
                    self._stacked_comp_mask = jax.device_put(
                        self._stacked_comp_mask, eng.client_sharding(self.mesh)
                    )
            return
        if self.compression.error_feedback:
            for client in self.clients:
                client.ef_residual = jax.tree.map(jnp.zeros_like, self._init_lora)

    def _probe_sensitivity(self, fl):
        """Per-client layer-sensitivity probe (Eq. 9-10) + lossless-fraction
        estimation, aggregated server-side (Eq. 11). Returns
        ``(global_scores, fractions, ns)``."""
        sensitivity, span = self._sensitivity_fn(), self.tel.span
        layer_scores_all, fractions, ns = [], [], []
        for ci, client in enumerate(self.clients):
            with span("sensitivity_client", cat="fl", track="server", args={"ci": ci}):
                ids = client.batches[int(client.order[0])]
                batch = self._client_batch(client, ids)
                scores = sensitivity(self.params, client.lora, batch)
                with span("sensitivity_read", cat="fl", track="server"):
                    client.layer_scores = np.asarray(scores)
                layer_scores_all.append(client.layer_scores)
                ns.append(client.n)

                # --- lossless fraction (only if not overridden; costly) ---
                if fl.gal_fraction is None or fl.sparse_ratio is None:
                    client.lossless_fraction = galmod.lossless_rank_fraction(
                        self.loss_fn,
                        self.params,
                        client.lora,
                        batch,
                        jax.random.fold_in(self.key, 1000 + ci),
                        iters=fl.lanczos_iters,
                    )
                fractions.append(
                    client.lossless_fraction
                    if fl.gal_fraction is None
                    else fl.gal_fraction
                )
        return galmod.aggregate_layer_scores(layer_scores_all, ns), fractions, ns

    def init_phase(self, *, probe_batches: int = 1) -> None:
        with self.tel.span("init_phase", cat="fl", track="server"):
            self._init_phase_body(probe_batches=probe_batches)

    def _init_phase_body(self, *, probe_batches: int = 1) -> None:
        fl = self.fl

        # --- curriculum difficulty (lines 2-5) ---
        with self.tel.span("difficulty", cat="fl", track="server"):
            self._compute_difficulty()

        # --- layer sensitivity scores (Eq. 9-10) + lossless fractions ---
        with self.tel.span("sensitivity", cat="fl", track="server"):
            if (
                self._oocore
                and fl.gal_fraction is not None
                and fl.sparse_ratio is not None
                and self.gal_mode in ("full", "random")
            ):
                # population-scale fast path: with both fractions pinned and
                # a score-blind GAL mode, the per-client sensitivity probe
                # could only feed scores nobody reads — skip it instead of
                # faulting every cold client in. Sample counts come from the
                # store (one cheap pass, no state materialization); the GAL
                # selection below is identical to what an in-memory run with
                # this config computes (n_star depends only on the pinned
                # fractions, and full/random ignore the scores).
                global_scores = np.zeros(lora_num_logical_layers(self.cfg))
                ns = [int(n) for n in self.store.sample_counts()]
                fractions = [fl.gal_fraction] * len(ns)
            else:
                global_scores, fractions, ns = self._probe_sensitivity(fl)

        # --- server: GAL selection (lines 6-7) ---
        L = len(global_scores)
        n_star = galmod.gal_layer_count(fractions, ns, L, fl.mu_global_local)
        self.gal_layers = self._select_layers(global_scores, n_star)
        self._gal_mask_tree = gal_mask_tree(self.cfg, self.global_lora, self.gal_layers)
        if self.mesh is not None:
            self._gal_mask_tree = jax.device_put(
                self._gal_mask_tree, eng.replicated_sharding(self.mesh)
            )
        self._gal_leaf_cache = None
        self._comm_bytes_cache = {}
        self._comp_mask_cache = {}

        # --- local update parameter selection (lines 8-10) ---
        if self.sparse_update:
            with self.tel.span("fim_warmup", cat="fl", track="server"):
                self._select_local_masks()

        # --- resource-adaptive rank: fold keep-masks into update masks ---
        if self.client_ranks is not None:
            self._fold_rank_masks()

        # --- compression state: EF residuals are support-dependent on the
        # GAL mask, so a re-init resets them; top-k count masks likewise ---
        self._reset_compression_state()

    def _select_layers(self, global_scores: np.ndarray, n_star: int) -> np.ndarray:
        L = len(global_scores)
        mode = self.gal_mode
        if mode == "full":
            return np.ones(L, bool)
        if mode == "random":
            mask = np.zeros(L, bool)
            mask[self.rng.choice(L, n_star, replace=False)] = True
            return mask
        if mode == "ascending":  # ablation AO: *least* important layers
            order = np.argsort(global_scores)
            mask = np.zeros(L, bool)
            mask[order[:n_star]] = True
            return mask
        if mode in ("importance", "descending"):  # DO == ours' ordering
            return galmod.select_gal_layers(global_scores, n_star)
        raise ValueError(mode)

    # ------------------------------------------------------------------
    # tuning phase (Alg. 1 lines 11-19)
    # ------------------------------------------------------------------

    def _merge_global(self, client: ClientState):
        """Line 15: overwrite the GAL part of the client's LoRA."""
        m = self._gal_mask_tree
        client.lora = jax.tree.map(
            # float mask arithmetic must not silently widen bf16 LoRA leaves
            lambda g, l, mm: (mm * g + (1.0 - mm) * l).astype(l.dtype),
            self.global_lora, client.lora, m,
        )

    def _gal_leaf_values(self) -> List[tuple]:
        """Per GAL-mask leaf: (unmasked value count, wire itemsize from the
        LoRA leaf's *actual* dtype). GAL mask leaves are broadcastable —
        one entry per layer slice, not per value — so each nonzero entry
        covers ``leaf.size // mask.size`` values.

        The mask is fixed after init_phase; sum it once, not every round
        (each ``float()`` is a device sync on the round's critical path).
        """
        if self._gal_leaf_cache is None:
            masks = jax.tree.leaves(self._gal_mask_tree)
            loras = jax.tree.leaves(self.global_lora)
            self._gal_leaf_cache = [
                (
                    int(float(jnp.sum(mm))) * (leaf.size // mm.size),
                    jnp.asarray(leaf).dtype.itemsize,
                )
                for mm, leaf in zip(masks, loras)
            ]
        return self._gal_leaf_cache

    def _client_comm_bytes(self, ci: Optional[int]) -> tuple:
        """(down, up) wire bytes of ONE completion event for client ``ci``
        (``None`` = a full-rank client): the pull ships the client's
        rank-projection of the unmasked GAL values raw; the push ships the
        compressed payload (values + scales + top-k indices) under
        ``self.compression``. Cached per distinct rank.
        """
        from repro.federated.compress import leaf_upload_bytes

        rank = (
            None
            if ci is None or self.client_ranks is None
            else int(self.client_ranks[ci])
        )
        if rank not in self._comm_bytes_cache:
            R = self.cfg.lora_rank
            down = up = 0
            for n, itemsize in self._gal_leaf_values():
                # every GAL leaf's value count is divisible by the rank (the
                # rank axis is a full dimension of both a and b), so the
                # rank projection is exact integer arithmetic
                n_r = n if rank is None else (n * rank) // R
                down += n_r * itemsize
                up += leaf_upload_bytes(n_r, itemsize, self.compression)
            self._comm_bytes_cache[rank] = (down, up)
        return self._comm_bytes_cache[rank]

    def _gal_bytes_per_client(self) -> int:
        """comm accounting for ONE full-rank completion event: GAL LoRA
        down (pull) + up (push). The async engine attributes bytes per
        completion — a dropped client that never reports back contributes
        nothing."""
        down, up = self._client_comm_bytes(None)
        return down + up

    def _gal_bytes(self, chosen) -> tuple:
        """Synchronous-round comm (total, upload-only) over the cohort."""
        pairs = [self._client_comm_bytes(int(ci)) for ci in chosen]
        return sum(d + u for d, u in pairs), sum(u for _, u in pairs)

    def _compress_client(self, ci: int, client: ClientState, pulled: Any):
        """Simulate the compressed upload channel for one client (loop and
        async engines): fake-quantize the masked GAL delta (adding the
        carried error-feedback residual first), store the new residual, and
        return the dequantized delta the server receives. The quantizer
        maps 0 → 0, so the result stays supported on the GAL mask.
        """
        comp = self.compression
        delta = jax.tree.map(
            lambda nl, g, mm: (nl - g) * mm,
            client.lora, pulled, self._gal_mask_tree,
        )
        res = client.ef_residual if comp.error_feedback else None
        cm = None
        if comp.use_thresh:
            cm = (
                self._comp_mask(ci)
                if self.client_ranks is not None
                else self._gal_mask_tree
            )
        y, new_res = kops.fake_compress(
            delta, res, cm,
            qmax=comp.qmax,
            topk_ratio=comp.topk_ratio,
            use_thresh=comp.use_thresh,
        )
        if comp.error_feedback:
            client.ef_residual = new_res
        return y

    def run_round(self, t: int, lr: Optional[float] = None) -> Dict[str, float]:
        if not self.tel.enabled:
            with self.tel.span("round"):  # the profiler annotation alone
                return self._dispatch_round(t, lr)
        tel = self.tel
        start = tel.tracer.now()
        with tel.span(
            "round", cat="fl", track="server",
            args={"t": t, "engine": self.engine},
        ) as sargs:
            stats = self._dispatch_round(t, lr)
            sargs["loss"] = stats.get("loss")
            sargs["comm_bytes"] = stats.get("comm_bytes")
        dur = tel.tracer.now() - start
        m = tel.metrics
        m.counter("fl.rounds").inc()
        m.histogram("fl.round_s").observe(dur)
        if self.comm_bytes_per_round:
            m.counter("fl.comm_bytes").inc(self.comm_bytes_per_round[-1])
            m.counter("fl.comm_upload_bytes").inc(
                self.comm_upload_bytes_per_round[-1]
            )
        # retrace visibility: resident traced signatures of this engine's
        # round-level program (pow2 step bucketing should keep this small)
        if self._async:
            m.gauge("jit.client_train_traces").set(
                eng.trace_cache_size(self._client_train_fn())
            )
        elif self._stacked_engine and not self._oocore:
            m.gauge("jit.round_fn_traces").set(
                eng.trace_cache_size(self._round_fn())
            )
            if self.mesh is None:
                m.gauge("jit.packed_round_fn_traces").set(
                    eng.trace_cache_size(self._packed_round_fn())
                )
        return stats

    def _dispatch_round(self, t: int, lr: Optional[float] = None) -> Dict[str, float]:
        if self._async:
            return self._run_round_async(t, lr)
        if self._stacked_engine:
            if self._oocore:
                return self._run_round_cohort(t, lr)
            return self._run_round_vectorized(t, lr)
        return self._run_round_loop(t, lr)

    def _run_round_loop(self, t: int, lr: Optional[float] = None) -> Dict[str, float]:
        fl = self.fl
        lr = fl.learning_rate if lr is None else lr
        k = min(fl.devices_per_round, len(self.clients))
        chosen = self.rng.choice(len(self.clients), k, replace=False)
        losses = []
        updates, weights, sel_counts = [], [], []
        step = self._grad_step()
        # the pulled global this cohort trains against: needed live for
        # delta extraction under compression (self.global_lora is only
        # reassigned after the host-side FedAvg below, so this is an alias)
        g0 = self.global_lora
        for ci in chosen:
            client = self.clients[ci]
            self._merge_global(client)
            sel = curr.selected_batch_ids(self.schedule, t, client.order)
            sel_counts.append(len(sel))
            for _ in range(fl.local_epochs):
                for j in sel:
                    ids = client.batches[int(j)]
                    batch = self._client_batch(client, ids)
                    loss, client.lora, client.opt_state = step(
                        self.params, client.lora, client.opt_state, batch, lr,
                        client.neuron_mask,
                    )
                    losses.append(float(loss))
            if self.compression is not None:
                y = self._compress_client(int(ci), client, g0)
                # value-form payload: the server's weighted GAL average of
                # (g0 + y_i) equals the delta merge g0 + Σ w_i y_i exactly
                updates.append(
                    jax.tree.map(lambda g, yy: (g + yy).astype(g.dtype), g0, y)
                )
            else:
                updates.append(client.lora)
            weights.append(client.n)
        # for scenario replay (benchmarks price the sync barrier): who ran,
        # and how many real local steps each took
        self.last_round_info = {
            "chosen": np.asarray(chosen),
            "client_steps": np.asarray(sel_counts) * fl.local_epochs,
        }

        # --- server aggregation over GAL (line 18, FedAvg) ---
        w = np.asarray(weights, np.float64)
        w = w / w.sum()
        m = self._gal_mask_tree

        def agg(g_old, mask, *client_loras):
            acc = sum(wi * cl for wi, cl in zip(w, client_loras))
            return (mask * acc + (1.0 - mask) * g_old).astype(g_old.dtype)

        self.global_lora = jax.tree.map(agg, self.global_lora, m, *updates)

        total, up = self._gal_bytes(chosen)
        self.comm_bytes_per_round.append(total)
        self.comm_upload_bytes_per_round.append(up)
        return {
            "loss": float(np.mean(losses)) if losses else float("nan"),
            # cohort mean: a per-client count would track whichever client
            # happened to be drawn last, not the curriculum schedule
            "selected_batches": float(np.mean(sel_counts)),
            "comm_bytes": float(self.comm_bytes_per_round[-1]),
        }

    def _run_round_vectorized(
        self, t: int, lr: Optional[float] = None
    ) -> Dict[str, float]:
        fl, tel = self.fl, self.tel
        lr = fl.learning_rate if lr is None else lr
        with _round_phase(tel, "plan"):
            k = min(fl.devices_per_round, len(self.clients))
            chosen = self.rng.choice(len(self.clients), k, replace=False)
            orders = [self.clients[ci].order for ci in chosen]
            batch_idx, step_valid = curr.step_plan(
                self.schedule, t, orders, fl.local_epochs
            )
            w = np.asarray([self.clients[ci].n for ci in chosen], np.float64)
            w = (w / w.sum()).astype(np.float32)
            # one device: pack the cohort's ragged step runs into fewer
            # lanes where the population's skew allows (the sharded engine's
            # lanes are the mesh's client axis)
            lanes = self._lane_plan(batch_idx, step_valid) if self.mesh is None else None

            if self._cohort_pad > k:
                # sharded engine: pad the cohort onto the stack's inert padding
                # rows (distinct indices keep the scatter free of duplicate
                # writes; zero weight and zero valid steps make them no-ops)
                pad_n = self._cohort_pad - k
                pad_rows = np.arange(len(self.clients), len(self.clients) + pad_n)
                chosen = np.concatenate([chosen, pad_rows])
                batch_idx = np.pad(batch_idx, ((0, pad_n), (0, 0)))
                step_valid = np.pad(step_valid, ((0, pad_n), (0, 0)))
                w = np.pad(w, (0, pad_n))

        with _round_phase(tel, "put"):
            mask_arg = (
                self._stacked_mask if self._stacked_mask is not None else jnp.zeros(())
            )
            args = (
                self.params,
                self.global_lora,
                self._stacked_lora,
                self._stacked_opt,
                mask_arg,
                self._gal_mask_tree,
                self._stack_data,
                self._sample_valid,
                jnp.asarray(chosen, jnp.int32),
                *map(jnp.asarray, (batch_idx, step_valid) if lanes is None else lanes),
                jnp.asarray(w),
                jnp.float32(lr),
            )
            if self.compression is not None:
                res_arg = (
                    self._stacked_residual
                    if self.compression.error_feedback
                    else jnp.zeros(())
                )
                cm_arg = (
                    self._stacked_comp_mask
                    if self._stacked_comp_mask is not None
                    else jnp.zeros(())
                )
                args += (res_arg, cm_arg)
        with _round_phase(tel, "dispatch"):
            round_fn = self._round_fn() if lanes is None else self._packed_round_fn()
            if self.compression is None:
                self.global_lora, self._stacked_lora, self._stacked_opt, losses = (
                    round_fn(*args)
                )
            else:
                (
                    self.global_lora,
                    self._stacked_lora,
                    self._stacked_opt,
                    losses,
                    new_res,
                ) = round_fn(*args)
                if self.compression.error_feedback:
                    self._stacked_residual = new_res

        with _round_phase(tel, "wait"):
            # a held-expert MoE's program returns (losses, counts)
            losses, counts = losses if isinstance(losses, tuple) else (losses, {})
            losses = np.asarray(losses)  # (S, lanes)
            counts = {name: float(np.sum(c)) for name, c in counts.items()}
        with _round_phase(tel, "account"):
            valid = (step_valid if lanes is None else lanes[2]).T
            mean_loss = float(np.sum(losses * valid) / max(np.sum(valid), 1.0))
            # how often packing engages, and the lane-steps the program ran
            runtime_metrics.counter(
                "fl.rounds_unpacked" if lanes is None else "fl.rounds_packed"
            ).inc()
            runtime_metrics.histogram("fl.round_scanned_steps").observe(losses.size)
            for name, total in counts.items():
                # the round's (token, held expert) assignments, the expert
                # rows its lane-steps computed and the held experts they ran
                # over every token
                runtime_metrics.histogram(f"fl.moe_{name}").observe(total)

            self.last_round_info = {
                "chosen": np.asarray(chosen[:k]),
                "client_steps": step_valid[:k].sum(axis=1).astype(np.int64),
            }
            total, up = self._gal_bytes(chosen[:k])
            self.comm_bytes_per_round.append(total)
            self.comm_upload_bytes_per_round.append(up)
            selected = np.mean(
                [len(curr.selected_batch_ids(self.schedule, t, o)) for o in orders]
            )
        return {
            "loss": mean_loss,
            "selected_batches": float(selected),
            "comm_bytes": float(self.comm_bytes_per_round[-1]),
            # compiled step-shape of this round (pow2-bucketed): the
            # curriculum-bucketing test asserts few distinct values per ramp
            "padded_steps": float(batch_idx.shape[1]),
        }

    def _run_round_cohort(
        self, t: int, lr: Optional[float] = None
    ) -> Dict[str, float]:
        """The vectorized round against an out-of-core client store.

        Same cohort draw, curriculum plan, FedAvg weighting, and comm
        accounting as ``_run_round_vectorized`` — but only the sampled
        cohort's states are fetched (pinned against eviction for the round),
        host-stacked to a leading k axis together with their streamed data
        grid (``stack_cohort``), trained by the cohort round program, and
        unstacked back into the store. Peak memory scales with the cohort
        and the store's hot set, never the population.
        """
        fl = self.fl
        lr = fl.learning_rate if lr is None else lr
        C = len(self.clients)
        k = min(fl.devices_per_round, C)
        chosen = self.rng.choice(C, k, replace=False)
        cohort = [int(ci) for ci in chosen]
        for ci in cohort:
            self.store.pin(ci)
        try:
            states = [self.clients[ci] for ci in cohort]
            orders = [s.order for s in states]
            batch_idx, step_valid = curr.step_plan(
                self.schedule, t, orders, fl.local_epochs
            )
            w = np.asarray([s.n for s in states], np.float64)
            w = (w / w.sum()).astype(np.float32)

            # the data grid is streamed per round: bucket the batch axis so
            # rounds with the same (k, NB, S) shape share a compiled program
            nb = max(len(s.batches) for s in states)
            grid = stack_cohort(
                [self.store.client_data(ci) for ci in cohort],
                fl.batch_size,
                pad_batches_to=bucket_size(nb),
            )
            data = {k_: jnp.asarray(v) for k_, v in grid.data.items()}
            sv = jnp.asarray(grid.sample_valid)

            def _stack(trees):
                return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)

            cohort_lora = _stack([s.lora for s in states])
            cohort_opt = _stack([s.opt_state for s in states])
            use_mask = states[0].neuron_mask is not None
            mask_arg = (
                _stack([s.neuron_mask for s in states])
                if use_mask
                else jnp.zeros(())
            )
            round_fn = self._cohort_round_fn(use_mask)
            args = (
                self.params,
                self.global_lora,
                cohort_lora,
                cohort_opt,
                mask_arg,
                self._gal_mask_tree,
                data,
                sv,
                jnp.asarray(batch_idx),
                jnp.asarray(step_valid),
                jnp.asarray(w),
                jnp.float32(lr),
            )
            new_res = None
            if self.compression is None:
                self.global_lora, new_lora, new_opt, losses = round_fn(*args)
            else:
                ef = self.compression.error_feedback
                res_arg = (
                    _stack([s.ef_residual for s in states]) if ef else jnp.zeros(())
                )
                cm_arg = (
                    _stack([self._comp_mask(ci) for ci in cohort])
                    if self._compress_static()["has_comp_mask"]
                    else jnp.zeros(())
                )
                self.global_lora, new_lora, new_opt, losses, res_out = round_fn(
                    *args, res_arg, cm_arg
                )
                if ef:
                    new_res = res_out
            for i, (ci, s) in enumerate(zip(cohort, states)):
                s.lora = jax.tree.map(lambda x, i=i: x[i], new_lora)
                s.opt_state = jax.tree.map(lambda x, i=i: x[i], new_opt)
                if new_res is not None:
                    s.ef_residual = jax.tree.map(lambda x, i=i: x[i], new_res)
                self.store.put(ci, s)
        finally:
            for ci in cohort:
                self.store.unpin(ci)

        losses = np.asarray(losses)  # (S, k)
        valid = step_valid.T
        mean_loss = float(np.sum(losses * valid) / max(np.sum(valid), 1.0))

        self.last_round_info = {
            "chosen": np.asarray(chosen),
            "client_steps": step_valid.sum(axis=1).astype(np.int64),
        }
        total, up = self._gal_bytes(chosen)
        self.comm_bytes_per_round.append(total)
        self.comm_upload_bytes_per_round.append(up)
        return {
            "loss": mean_loss,
            "selected_batches": float(
                np.mean(
                    [
                        len(curr.selected_batch_ids(self.schedule, t, o))
                        for o in orders
                    ]
                )
            ),
            "comm_bytes": float(self.comm_bytes_per_round[-1]),
            "padded_steps": float(batch_idx.shape[1]),
        }

    # ------------------------------------------------------------------
    # async engine (event-driven, straggler-aware)
    # ------------------------------------------------------------------

    def _ensure_scheduler(self):
        if self._scheduler is None:
            from repro.federated.async_agg import AsyncScheduler
            from repro.federated.hetero import SCENARIO_SEED_OFFSET

            # scenario randomness rides its own stream so heterogeneity
            # never perturbs cohort sampling (self.rng) equivalence
            bound = self.scenario.bind(
                len(self.clients), seed=self._seed + SCENARIO_SEED_OFFSET
            )
            self._scheduler = AsyncScheduler(
                num_clients=len(self.clients),
                cohort_size=min(self.fl.devices_per_round, len(self.clients)),
                scenario=bound,
                rng=self.rng,
                cfg=self.async_cfg,
                # wall-clock-aware sampling interpolates on the curriculum
                # ramp: prefer fast clients early, uniform once data is full
                progress=self.schedule.progress,
                telemetry=self.tel,
            )
        return self._scheduler

    def _async_callbacks(self, lr, sched):
        """(plan, train) closures handed to the event scheduler.

        Both apply the same step-count adaptation (``adapt_steps``): a
        client ``r`` times slower than the fastest trains the easiest
        ``ceil(n/r)`` of its selected curriculum batches, so ``plan`` (drop
        timing) and ``train`` (the real local round) price identically. In
        delta merge mode ``train`` also extracts the client's delta against
        the pulled version while that version is still alive.
        """
        from repro.federated.async_agg import ClientUpdate, adapted_step_count

        fl, cfg = self.fl, self.async_cfg
        train_fn = self._client_train_fn()
        use_mask = self.clients[0].neuron_mask is not None
        delta_mode = cfg.merge_mode == "delta"
        comp = self.compression

        def _cap(ci: int, n_sel: int) -> Optional[int]:
            if not cfg.adapt_steps:
                return None
            # pace_mode picks the relative-speed signal: the scenario's
            # ground truth, or the scheduler's per-client EMA of observed
            # completion times (scenario-free, so it works in deployment)
            rel = (
                sched.observed_rel_speed(ci)
                if cfg.pace_mode == "observed"
                else sched.scenario.rel_speed(ci)
            )
            return adapted_step_count(n_sel, rel, cfg.min_steps)

        def plan(ci: int, t: int) -> int:
            sel = curr.selected_batch_ids(self.schedule, t, self.clients[ci].order)
            cap = _cap(ci, len(sel))
            n_sel = len(sel) if cap is None else min(cap, len(sel))
            return n_sel * fl.local_epochs

        def _client_grid_row(ci: int, client: ClientState):
            """One client's padded (NB, B, ...) data grid row + valid mask.

            In-memory engines pre-stack the whole population once; the
            out-of-core store streams the dispatched client's shard through
            ``stack_cohort`` on demand (batch axis pow2-bucketed, so the
            per-client train program compiles once per bucket, and padded
            rows are never indexed — ``batch_idx`` only holds real ids).
            """
            if not self._oocore:
                return (
                    {k_: v[ci] for k_, v in self._stack_data.items()},
                    self._sample_valid[ci],
                )
            row = stack_cohort(
                [self.store.client_data(ci)],
                fl.batch_size,
                pad_batches_to=bucket_size(len(client.batches)),
            )
            return (
                {k_: jnp.asarray(v[0]) for k_, v in row.data.items()},
                jnp.asarray(row.sample_valid[0]),
            )

        def train(ci: int, t: int, version: int) -> ClientUpdate:
            # pinned while in flight / buffered: the async aggregator may
            # hold this client's payload across several flushes, and eviction
            # churn on active clients would thrash the hot set (the runner
            # re-syncs pins to in-flight|buffered after every merge)
            self.store.pin(ci)
            client = self.clients[ci]
            n_sel = len(curr.selected_batch_ids(self.schedule, t, client.order))
            cap = _cap(ci, n_sel)
            batch_idx, step_valid = curr.step_plan(
                self.schedule, t, [client.order], fl.local_epochs,
                max_selected=None if cap is None else [cap],
            )
            mask_arg = client.neuron_mask if use_mask else jnp.zeros(())
            cdata, csv = _client_grid_row(ci, client)
            pulled = self._global.front  # the version this client pulls
            lora_arg, opt_arg = client.lora, client.opt_state
            if self._oocore:
                # Out of core, a client's state buffers chain directly from
                # one train call's (donation-aliased) outputs into the next
                # call's donated inputs — the only such lineage in the repo
                # (cohort rounds re-stack state into fresh buffers every
                # round). On XLA:CPU with a warm persistent compilation
                # cache that chain corrupts neighbouring live buffers
                # (observed: the pulled global going non-finite one round
                # later), so break it: donate fresh copies instead. The
                # copies are rank-r per-client trees — noise next to the
                # train step — and the executable still recycles them via
                # its input/output aliases.
                lora_arg = jax.tree.map(jnp.copy, lora_arg)
                opt_arg = jax.tree.map(jnp.copy, opt_arg)
            new_lora, new_opt, losses = train_fn(
                self.params,
                pulled,
                lora_arg,  # donated: the client trains in place
                opt_arg,  # donated
                mask_arg,
                self._gal_mask_tree,
                cdata,
                csv,
                jnp.asarray(batch_idx[0]),
                jnp.asarray(step_valid[0]),
                jnp.float32(lr),
            )
            client.lora, client.opt_state = new_lora, new_opt
            self.store.put(ci, client)
            # delta against the pulled version, extracted now — by merge
            # time this version may already be retired from the double
            # buffer (staleness >= 2), so it cannot be recovered later
            if comp is None:
                delta = self._delta_fn()(new_lora, pulled) if delta_mode else None
                lora_payload = new_lora
            else:
                # the channel carries the compressed GAL delta either way;
                # buffered mode reconstructs pulled + dequantized server-side
                y = self._compress_client(ci, client, pulled)
                delta = y if delta_mode else None
                lora_payload = (
                    new_lora
                    if delta_mode
                    else jax.tree.map(
                        lambda g, yy: (g + yy).astype(g.dtype), pulled, y
                    )
                )
            down, up = self._client_comm_bytes(ci)
            n_steps = int(step_valid.sum())
            return ClientUpdate(
                client=ci,
                lora=lora_payload,
                delta=delta,
                losses=losses,
                step_valid=step_valid[0],
                n_samples=client.n,
                n_steps=n_steps,
                n_selected=n_steps // fl.local_epochs,
                pulled_version=version,
                round_t=t,
                comm_bytes=down + up,
                upload_bytes=up,
            )

        return plan, train

    def _run_round_async(self, t: int, lr: Optional[float] = None) -> Dict[str, float]:
        """One buffer flush = one server round.

        The scheduler advances its virtual clock (dispatching replacements,
        absorbing drops) until any ``buffer_size`` clients have reported;
        their GAL layers merge into a fresh double-buffered global with
        staleness-discounted FedAvg weights. Comm bytes are attributed per
        completion event, so dropped clients cost nothing and the
        homogeneous full-cohort configuration reproduces the synchronous
        engines' accounting exactly.
        """
        fl = self.fl
        lr = fl.learning_rate if lr is None else lr
        sched = self._ensure_scheduler()
        plan, train = self._async_callbacks(lr, sched)
        result = sched.run_until_merge(t, plan, train)

        if self.async_cfg.merge_mode == "delta":
            payloads = [u.delta for u in result.updates]
            merge = self._delta_merge_fn()
        else:
            payloads = [u.lora for u in result.updates]
            merge = self._merge_fn()
        if self._hierarchy is not None:
            # two-tier topology: edges reduce their regions' payloads to
            # partial weighted sums, the server merges the summaries with
            # unit weights — bit-exact to the flat merge at one edge, equal
            # up to float reassociation otherwise (see federated.hierarchy)
            from repro.federated.hierarchy import build_edge_summary_fn, edge_reduce

            summary_fn = _memo(("edge_summary",), build_edge_summary_fn)
            stacked, wts = edge_reduce(
                summary_fn,
                payloads,
                np.asarray(result.weights),
                [u.client for u in result.updates],
                len(self.clients),
                self._hierarchy.num_edges,
                assignments=self._hierarchy.assignments,
            )
        else:
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *payloads)
            wts = jnp.asarray(result.weights, jnp.float32)
        new_global = merge(
            self._global.front,
            self._gal_mask_tree,
            stacked,
            wts,
        )
        self._global.publish(new_global)
        self.global_lora = self._global.front
        # release merged/dropped clients for eviction; whoever is still in
        # flight or sitting in the next buffer stays pinned
        self.store.sync_pins(
            set(sched.in_flight) | {u.client for u in sched.buffer}
        )

        num = den = 0.0
        for u in result.updates:
            losses = np.asarray(u.losses, np.float64)
            valid = np.asarray(u.step_valid, np.float64)
            num += float(np.sum(losses * valid))
            den += float(np.sum(valid))

        # completions pay the round trip whether or not the staleness cutoff
        # later discards them — the bytes were already on the wire (the
        # cutoff's casualties never reach us, so the scheduler accumulates
        # their payload bytes and reports them on the MergeResult)
        self.comm_bytes_per_round.append(
            sum(u.comm_bytes for u in result.updates) + result.stale_dropped_bytes
        )
        self.comm_upload_bytes_per_round.append(
            sum(u.upload_bytes for u in result.updates)
            + result.stale_dropped_upload_bytes
        )
        return {
            "loss": num / max(den, 1.0),
            "selected_batches": float(
                np.mean([u.n_selected for u in result.updates])
            ),
            "comm_bytes": float(self.comm_bytes_per_round[-1]),
            "virtual_time": float(result.clock),
            "staleness_mean": float(result.staleness.mean()),
            "merged_clients": float(result.completed),
            "dropped_clients": float(result.dropped),
            "stale_dropped": float(result.stale_dropped),
            "buffer_size": float(sched.buffer_size),
            "padded_steps": float(
                max(len(np.asarray(u.step_valid)) for u in result.updates)
            ),
        }

    # ------------------------------------------------------------------
    # run checkpointing (repro.checkpoint.federation)
    # ------------------------------------------------------------------

    def checkpoint_state(self):
        """``(host, arrays, files)`` — everything a fresh runner needs to
        continue this run exactly where it stands.

        ``host`` is JSON-able (config fingerprint for validation, the cohort
        RNG state, comm accounting, the async scheduler's bookkeeping);
        ``arrays`` is one nested dict of numpy/JAX arrays (global LoRA, GAL
        selection, client state — stacked trees, per-client trees, or the
        out-of-core store's resident metadata, depending on engine/store);
        ``files`` maps cold-file names to paths for the checkpoint writer to
        hardlink (out-of-core store only). Deliberately NOT captured:
        anything derivable from the constructor args (params, data stacks,
        batches, schedules, compiled programs) and per-client momentum FIMs
        on the in-memory stacked engines (write-only diagnostics after
        ``init_phase``; the store engines spill them anyway).
        """
        from repro.federated.store import OutOfCoreStore

        host: Dict[str, Any] = {
            "engine": self.engine,
            "num_clients": len(self.clients),
            "seed": int(self._seed),
            "optimizer": self.optimizer_name,
            "initialized": self.gal_layers is not None,
            "rng_state": self.rng.bit_generator.state,
            "comm_bytes_per_round": [int(x) for x in self.comm_bytes_per_round],
            "comm_upload_bytes_per_round": [
                int(x) for x in self.comm_upload_bytes_per_round
            ],
        }
        arrays: Dict[str, Any] = {"global_lora": self.global_lora}
        files: Dict[str, str] = {}
        if self.gal_layers is not None:
            arrays["gal_layers"] = np.asarray(self.gal_layers, bool)

        if self._oocore:
            s_host, s_arrays, files = self.store.checkpoint_state()
            host["store"] = s_host
            if s_arrays:
                arrays["store"] = s_arrays
        elif self._stacked_engine:
            stacked: Dict[str, Any] = {"lora": self._stacked_lora}
            opt_empty = (
                isinstance(self._stacked_opt, dict) and not self._stacked_opt
            )
            if not opt_empty:
                stacked["opt"] = self._stacked_opt
            for name, tree in (
                ("mask", self._stacked_mask),
                ("residual", self._stacked_residual),
                ("comp_mask", self._stacked_comp_mask),
            ):
                if tree is not None:
                    stacked[name] = tree
            arrays["stacked"] = stacked
            host["stacked"] = {
                "opt_empty": opt_empty,
                "has_mask": self._stacked_mask is not None,
                "has_residual": self._stacked_residual is not None,
                "has_comp_mask": self._stacked_comp_mask is not None,
            }
            host["clients"], carrs = self._checkpoint_client_meta()
            if carrs:
                arrays["clients"] = carrs
        else:  # loop / async on the in-memory store: concrete per-client trees
            clients_host, carrs = self._checkpoint_client_meta()
            for ci, client in enumerate(self.clients):
                fields, trees = OutOfCoreStore._split_state(client)
                clients_host[str(ci)]["fields"] = fields
                if trees:
                    carrs.setdefault(str(ci), {})["trees"] = trees
            host["clients"] = clients_host
            if carrs:
                arrays["clients"] = carrs

        if self._async:
            a_host: Dict[str, Any] = {
                "global_version": int(self._global.version),
                "has_back": self._global.back is not None,
                "scheduler": None,
            }
            a_arrays: Dict[str, Any] = {}
            if self._global.back is not None:
                a_arrays["back"] = self._global.back
            if self._scheduler is not None:
                s_host, s_arrays = self._scheduler.checkpoint_state()
                a_host["scheduler"] = s_host
                if s_arrays:
                    a_arrays["scheduler"] = s_arrays
            host["async"] = a_host
            if a_arrays:
                arrays["async"] = a_arrays
        return host, arrays, files

    def _checkpoint_client_meta(self):
        """Host-side curriculum metadata of every client (in-memory stores).

        ``order``/``difficulty``/``layer_scores`` go to arrays;
        ``lossless_fraction`` rides in host. ``n``/``batches`` are derived
        from the data shards at construction, so they are not captured.
        """
        clients_host: Dict[str, Any] = {}
        carrs: Dict[str, Any] = {}
        for ci, client in enumerate(self.clients):
            key = str(ci)
            clients_host[key] = {
                "lossless_fraction": float(client.lossless_fraction),
                "has_difficulty": client.difficulty is not None,
                "has_layer_scores": client.layer_scores is not None,
            }
            meta = {"order": np.asarray(client.order)}
            if client.difficulty is not None:
                meta["difficulty"] = np.asarray(client.difficulty)
            if client.layer_scores is not None:
                meta["layer_scores"] = np.asarray(client.layer_scores)
            carrs[key] = {"meta": meta}
        return clients_host, carrs

    def restore_state(self, host, arrays, *, store_files_dir: str = "") -> None:
        """Install a :meth:`checkpoint_state` snapshot on this runner.

        The runner must be freshly constructed with the same configuration
        the snapshot was taken under (engine, population, optimizer — the
        basics are validated; the rest is the caller's contract) and must
        NOT have run ``init_phase`` or any round: restore *replaces* state,
        it does not merge. ``store_files_dir`` points at the checkpoint's
        cold-file directory (out-of-core store only).
        """
        from repro.federated.store import SPILL_FIELDS

        for field, mine in (
            ("engine", self.engine),
            ("num_clients", len(self.clients)),
            ("optimizer", self.optimizer_name),
        ):
            if host[field] != mine:
                raise ValueError(
                    f"checkpoint was taken with {field}={host[field]!r}; "
                    f"this runner has {mine!r}"
                )
        self.rng.bit_generator.state = host["rng_state"]
        self.comm_bytes_per_round = [int(x) for x in host["comm_bytes_per_round"]]
        self.comm_upload_bytes_per_round = [
            int(x) for x in host["comm_upload_bytes_per_round"]
        ]
        repl_shd = (
            eng.replicated_sharding(self.mesh) if self.mesh is not None else None
        )
        client_shd = (
            eng.client_sharding(self.mesh) if self.mesh is not None else None
        )

        def _dev(tree, shd=None):
            # jnp.array, not asarray: restored leaves must own their buffers.
            # On CPU asarray can alias the numpy arrays backing the loaded
            # npz, and the vectorized round *donates* the stacked trees —
            # donating an aliased buffer lets XLA write through freed host
            # memory (segfault).
            tree = jax.tree.map(jnp.array, tree)
            return tree if shd is None else jax.device_put(tree, shd)

        self.global_lora = _dev(arrays["global_lora"], repl_shd)
        if host["initialized"]:
            self.gal_layers = np.asarray(arrays["gal_layers"], bool)
            self._gal_mask_tree = gal_mask_tree(
                self.cfg, self.global_lora, self.gal_layers
            )
            if repl_shd is not None:
                self._gal_mask_tree = jax.device_put(self._gal_mask_tree, repl_shd)
        else:
            self.gal_layers = None
            self._gal_mask_tree = None
        # derived caches keyed on the GAL selection: rebuild lazily
        self._gal_leaf_cache = None
        self._comm_bytes_cache = {}
        self._comp_mask_cache = {}

        if self._oocore:
            self.store.restore_checkpoint_state(
                host["store"], arrays.get("store", {}), store_files_dir
            )
        elif self._stacked_engine:
            st_host, st = host["stacked"], arrays["stacked"]
            self._stacked_lora = _dev(st["lora"], client_shd)
            self._stacked_opt = {} if st_host["opt_empty"] else _dev(
                st["opt"], client_shd
            )
            self._stacked_mask = (
                _dev(st["mask"], client_shd) if st_host["has_mask"] else None
            )
            self._stacked_residual = (
                _dev(st["residual"], client_shd)
                if st_host["has_residual"]
                else None
            )
            self._stacked_comp_mask = (
                _dev(st["comp_mask"], client_shd)
                if st_host["has_comp_mask"]
                else None
            )
            self._restore_client_meta(host["clients"], arrays.get("clients", {}))
            for ci, client in enumerate(self.clients):
                # lora stays a lazy view into the restored stack (the view
                # closure reads the live property); masks re-slice it
                client.neuron_mask = (
                    None
                    if self._stacked_mask is None
                    else jax.tree.map(
                        lambda x, ci=ci: x[ci], self._stacked_mask
                    )
                )
        else:
            self._restore_client_meta(host["clients"], arrays.get("clients", {}))
            carrs = arrays.get("clients", {})
            for ci, client in enumerate(self.clients):
                key = str(ci)
                fields = host["clients"][key]["fields"]
                trees = carrs.get(key, {}).get("trees", {})
                for field in SPILL_FIELDS:
                    status = fields[field]
                    if status == "none":
                        value = None
                    elif status == "empty":
                        value = {}
                    else:
                        value = _dev(trees[field])
                    if field == "_lora":
                        client.lora = value  # setter also clears any view
                    else:
                        setattr(client, field, value)
                self.store.put(ci, client)

        if self._async:
            from repro.federated.async_agg import DoubleBufferedGlobal

            a_host = host["async"]
            a_arrays = arrays.get("async", {})
            self._global = DoubleBufferedGlobal(self.global_lora)
            self._global.version = int(a_host["global_version"])
            if a_host["has_back"]:
                self._global.back = _dev(a_arrays["back"])
            if a_host["scheduler"] is not None:
                sched = self._ensure_scheduler()
                sched.restore_checkpoint_state(
                    a_host["scheduler"], a_arrays.get("scheduler", {})
                )
                self.store.sync_pins(
                    set(sched.in_flight) | {u.client for u in sched.buffer}
                )

    def _restore_client_meta(self, clients_host, carrs) -> None:
        for ci, client in enumerate(self.clients):
            key = str(ci)
            m = clients_host[key]
            meta = carrs.get(key, {}).get("meta", {})
            client.order = np.asarray(meta["order"])
            client.lossless_fraction = float(m["lossless_fraction"])
            client.difficulty = (
                np.asarray(meta["difficulty"]) if m["has_difficulty"] else None
            )
            client.layer_scores = (
                np.asarray(meta["layer_scores"]) if m["has_layer_scores"] else None
            )

    # ------------------------------------------------------------------
    # evaluation
    # ------------------------------------------------------------------

    def evaluate(self, data: Dict[str, np.ndarray], batch_size: int = 32) -> float:
        """Accuracy with the *server* model (GAL part global, rest zeros)."""
        forward, family = self.model.forward, self.cfg.family

        def build():
            def predict(params, lora, batch):
                logits, _ = forward(params, lora, batch)
                if family == "encoder":
                    return jnp.argmax(logits, -1)
                return jnp.argmax(logits[:, -1], -1)

            return jax.jit(predict)

        predict = _memo(("eval", forward), build)
        n = len(next(iter(data.values())))
        correct, total = 0, 0
        for i in range(0, n, batch_size):
            batch = {kk: v[i : i + batch_size] for kk, v in data.items()}
            pred = np.asarray(predict(self.params, self.global_lora, batch))
            gold = batch["labels"] if self.cfg.family == "encoder" else batch["label_token"]
            correct += int((pred == gold).sum())
            total += len(gold)
        return correct / max(total, 1)
