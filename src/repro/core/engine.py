"""Vectorized federated round engine — one jitted program per round.

The legacy loop engine (``FibecFed(engine="loop")``) dispatches one jitted
call per (client, batch) step, merges/aggregates LoRA trees on the host, and
blocks on a device sync every step to read the loss. This module compiles the
whole tuning round (Alg. 1 lines 11-19) into a single device program:

  gather the chosen clients' slices of the stacked client state
    -> merge the global GAL params into each client's LoRA (line 15)
    -> ``lax.scan`` over padded curriculum steps of a ``vmap`` over clients
       (lines 16-17, masked local SGD/AdamW)
    -> weighted GAL FedAvg fused into the same program (line 18)
    -> scatter the updated client state back into the stack

Client pytrees (LoRA / optimizer state / neuron masks) are stacked along a
leading client axis; client data lives on one padded ``(C, NB, B, ...)`` grid
(:func:`repro.data.pipeline.stack_clients`) with validity masks, so padded
samples and padded curriculum steps are exact no-ops and the vectorized
engine reproduces the loop engine's numerics. Donation recycles the
stacked buffers, so steady-state rounds allocate nothing persistent.

The initialization phase gets the same treatment: difficulty scoring runs as
one vmapped program over every (client, batch) cell, and the momentum-FIM
warmup is a scan over warmup epochs of a vmap over clients. Where those
programs would not fit the device, the runner builds them over chunks of
clients (``chunk=``, a ``lax.map`` of the vmap) with the same results.

One builder, :func:`build_round_fn`, jits every variant of the one round
body (:func:`_round_body`); the runner picks the variant, never a program:

- Mesh sharding (``engine="sharded"``, ``mesh=``): the leading client axis
  is the data-parallel axis of a device mesh. The stacked client state,
  data grid, and gathered cohort are sharded over the mesh's client axes
  (``launch.mesh.dp_axes``), base params and the global GAL LoRA
  replicated, and the fused weighted FedAvg lowers to an all-reduce (psum)
  over the client axis — the paper's server aggregation as a collective.
  Client counts must be padded to a multiple of the mesh's client-group
  count (``stack_clients(pad_clients_to=...)``); the runner also pads the
  chosen cohort with dedicated padding rows (zero weight, zero valid
  steps) so gather/scatter never write one row twice.
- Lane packing (``packed=True``, single device only): where the cohort's
  shards are skewed, the program trains the cohort in fewer ``vmap`` lanes
  than clients, each lane running several clients' curriculum steps back
  to back (:func:`repro.core.curriculum.pack_lanes`), so a short client no
  longer pads to the longest. The sharded program keeps one lane per
  client (its lanes are the mesh's client axis).
- Compressed uploads (``compress=``): the server aggregates fake-quantized
  GAL deltas, with per-client error-feedback residuals as stacked state.
- The out-of-core client store runs the unpacked program over a stack of
  just the cohort, with ``chosen = arange(k)``.

The round program decomposes into two separately-callable pieces shared by
every engine: :func:`make_client_step` (one masked local step) and
:func:`gal_weighted_merge` (the fused weighted GAL FedAvg). The async engine
(``repro.federated.async_agg``) recombines them without the vmap barrier:
:func:`build_client_train_fn` scans one client's whole local round as its
own jitted program, and :func:`build_merge_fn` jits the merge standalone so
the server can flush its completion buffer the moment any K clients report.
"""
from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import fisher as fish
from repro.launch.mesh import dp_axes
from repro.train.losses import masked_mean_loss


def trace_cache_size(fn: Any) -> int:
    """Distinct traced signatures resident in a jitted callable's cache.

    The retrace signal behind the ``jit.*_traces`` telemetry gauges: a round
    program that keeps retracing (e.g. un-bucketed step counts producing a
    new shape every round) shows up as a growing cache instead of a silent
    compile stall. Returns 0 for non-jitted callables or if the private
    accessor disappears — the gauge degrades, nothing breaks.
    """
    try:
        return int(fn._cache_size())
    except Exception:
        return 0


def _gather(tree, idx):
    return jax.tree.map(lambda x: x[idx], tree)


def _scatter(tree, idx, values, unique=False):
    return jax.tree.map(
        lambda s, c: s.at[idx].set(c, unique_indices=unique), tree, values
    )


def client_sharding(mesh) -> NamedSharding:
    """Stacked client trees: leading client axis over the mesh's dp axes."""
    return NamedSharding(mesh, P(dp_axes(mesh)))


def replicated_sharding(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _masked_loss(loss_fn: Callable) -> Callable:
    """Mask-aware batch loss. Prefer the loss's native ``.masked`` variant
    (one batched forward); fall back to the generic per-sample-vmap reduction
    — same value, but an order of magnitude slower per step."""
    native = getattr(loss_fn, "masked", None)
    if native is not None:
        return native
    return lambda params, lora, batch, sv: masked_mean_loss(
        loss_fn, params, lora, batch, sv
    )


def make_client_step(loss_fn: Callable, opt_update: Callable, *, stats: bool = False) -> Callable:
    """One client's masked local SGD/AdamW step (Alg. 1 lines 16-17).

    ``step(params, lora, opt, mask, batch, sample_valid, lr, active=None) ->
    (loss, new_lora, new_opt)``. This is the shared inner body: the round
    program vmaps it over the cohort, the async per-client train program
    scans it without the vmap barrier — both therefore share numerics by
    construction. ``active`` is the padded-step no-op predicate: the
    optimizer commits per entry (``eff = mask ⊙ active``), so an inactive
    step returns the carry unchanged — LoRA, moments, and Adam's step
    counter — without the separate ``tree_where`` pass the engines used to
    run over every leaf (and the fused kernels fold the predicate into their
    single read/write pass).

    With ``stats`` and a loss that keeps counts (``loss_fn.masked_stats``:
    the held-expert MoE), the step's loss comes back as ``(loss, counts)``:
    the model's counts, ``held_assignments`` zero on an inactive step (whose
    ``expert_rows`` were computed all the same); they carry no gradient.
    Otherwise ``stats`` changes nothing.
    """
    masked_stats = getattr(loss_fn, "masked_stats", None) if stats else None
    if masked_stats is not None:

        def stats_step(params, lora, opt, mask, batch, sample_valid, lr, active=None):
            (loss, st), grads = jax.value_and_grad(
                lambda x: masked_stats(params, x, batch, sample_valid), has_aux=True
            )(lora)
            with jax.named_scope("adamw"):
                new_lora, new_opt = opt_update(grads, opt, lora, lr, mask, active)
            if active is not None:
                held = st["held_assignments"]
                st = dict(st, held_assignments=held * active.astype(held.dtype))
            return (loss, st), new_lora, new_opt

        return stats_step
    masked = _masked_loss(loss_fn)

    def one_step(params, lora, opt, mask, batch, sample_valid, lr, active=None):
        loss, grads = jax.value_and_grad(
            lambda x: masked(params, x, batch, sample_valid)
        )(lora)
        # named for the paper's optimizer, whichever update this is
        with jax.named_scope("adamw"):
            new_lora, new_opt = opt_update(grads, opt, lora, lr, mask, active)
        return loss, new_lora, new_opt

    return one_step


def gal_weighted_merge(global_lora, gal_mask, stacked_client_lora, weights):
    """Fused weighted FedAvg over the GAL part only (Alg. 1 line 18).

    ``weights`` (k,) must already be normalized (the async aggregator folds
    its staleness discount in before normalizing); the contraction over the
    stacked client axis IS the server aggregation — under a sharded client
    axis it lowers to an all-reduce, called standalone it is the async
    buffer flush.
    """
    agg = jax.tree.map(
        lambda x: jnp.tensordot(weights, x, axes=1), stacked_client_lora
    )
    # the float mask/weight arithmetic must not silently widen bf16 leaves
    return jax.tree.map(
        lambda g, m, a: (m * a + (1.0 - m) * g).astype(g.dtype),
        global_lora, gal_mask, agg,
    )


def build_merge_fn() -> Callable:
    """Jitted :func:`gal_weighted_merge` — the async server's buffer flush.

    The old global is *not* donated: in-flight stragglers may still be
    training against it (the double-buffered front/back pair in
    ``federated.async_agg`` owns buffer lifetime, not XLA).
    """
    return jax.jit(gal_weighted_merge)


def lora_delta(new_lora, pulled_lora):
    """Client-side delta extraction for the FedAsync-style merge mode: the
    trained LoRA minus the global version the client pulled. Computed at
    completion time — while the pulled version is still alive in the double
    buffer — so the server never has to keep arbitrarily old versions
    around for stragglers. Only the GAL part is meaningful downstream (the
    merge masks the rest away)."""
    return jax.tree.map(lambda n, p: n - p, new_lora, pulled_lora)


def build_delta_fn() -> Callable:
    """Jitted :func:`lora_delta`. Neither argument is donated: the new LoRA
    is the client's live state and the pulled global may be shared by other
    in-flight clients."""
    return jax.jit(lora_delta)


def gal_delta_merge(global_lora, gal_mask, stacked_deltas, weights):
    """FedAsync-style delta application over the GAL part (merge_mode
    ``"delta"``): ``global += sum_i w_i * delta_i`` on GAL layers, identity
    elsewhere. ``weights`` are the *absolute* per-delta rates
    (``federated.async_agg.delta_weights``: server lr x sample weight x
    staleness discount, NOT renormalized) — a stale buffer moves the global
    less, which is the property the buffered value merge cannot express.
    At server lr 1 and staleness 0 the weights sum to 1 and this equals
    :func:`gal_weighted_merge` exactly.
    """
    agg = jax.tree.map(
        lambda x: jnp.tensordot(weights, x, axes=1), stacked_deltas
    )
    return jax.tree.map(
        lambda g, m, d: (g + m * d).astype(g.dtype), global_lora, gal_mask, agg
    )


def build_delta_merge_fn() -> Callable:
    """Jitted :func:`gal_delta_merge` — the delta-mode buffer flush. Like
    :func:`build_merge_fn`, the old global is not donated (the double
    buffer owns version lifetime)."""
    return jax.jit(gal_delta_merge)


def _round_body(
    loss_fn: Callable,
    opt_update: Callable,
    *,
    use_neuron_mask: bool,
    shard: Callable = lambda t: t,
    hoist_client_data: bool = False,
    compress: Any = None,
    packed: bool = False,
) -> Callable:
    """The round program of every stacked engine and client store.

    ``shard`` constrains gathered per-cohort trees (leading k axis) onto the
    mesh's client axes; identity on one device. ``hoist_client_data`` gathers
    the chosen clients' data grid once before the step scan (so the sharded
    engine pays one collective gather per round, not one per step) — the
    per-step batch values are identical either way.

    ``compress`` (a dict of ``qmax``/``topk_ratio``/``use_thresh``/
    ``error_feedback``/``has_comp_mask`` — trace-time constants) switches the
    server aggregation to the compressed-upload path: each chosen client's
    GAL delta (plus its carried error-feedback residual) goes through the
    fake-quantize/top-k round trip (:func:`repro.kernels.ops.fake_compress`)
    and the server applies the *reconstructions* delta-style — algebraically
    equal to the value merge when compression is lossless, since the
    normalized weights sum to one. The round program then takes two extra
    trailing arguments (the stacked residual state and an optional per-client
    top-k count mask) and returns the updated residuals as a fifth output.

    ``packed`` (single device only) trains the cohort in the lanes of a
    :func:`repro.core.curriculum.pack_lanes` plan: the program takes
    ``lane_client`` (L, S) after ``chosen``, ``batch_idx``/``step_valid`` are
    (L, S), and the losses come back (S, L). See :func:`build_round_fn`.

    With a loss that keeps counts (``loss_fn.masked_stats``), ``losses`` is
    the pair ``(losses, counts)``, each count shaped as the losses.
    """
    client_step = make_client_step(loss_fn, opt_update, stats=True)

    def lanes_step(params, lr, lora_c, opt_c, mask_c, batch, sv, active):
        # one curriculum step of every lane: the client step vmapped over
        # the leading lane axis
        def one_step(lo, op, mk, b, m, a):
            return client_step(params, lo, op, mk, b, m, lr, a)

        if use_neuron_mask:
            return jax.vmap(one_step)(lora_c, opt_c, mask_c, batch, sv, active)
        return jax.vmap(lambda lo, op, b, m, a: one_step(lo, op, None, b, m, a))(
            lora_c, opt_c, batch, sv, active
        )

    def train_cohort(params, lr, data, sample_valid, chosen, cl_lora, cl_opt, cl_mask,
                     batch_idx, step_valid):
        # one lane per chosen client, every lane as long as the longest; with
        # hoist_client_data, data and sample_valid are the cohort's own grid
        def step(carry, xs):
            lora_c, opt_c = carry
            bidx, active = xs  # (k,), (k,)
            if hoist_client_data:
                # per-client batch pick stays aligned on the k axis (no
                # cross-device gather inside the scan)
                batch = shard(
                    {kk: jax.vmap(lambda d, j: d[j])(v, bidx) for kk, v in data.items()}
                )
                sv = shard(jax.vmap(lambda d, j: d[j])(sample_valid, bidx))
            else:
                batch = {kk: v[chosen, bidx] for kk, v in data.items()}
                sv = sample_valid[chosen, bidx]
            # padded steps compute but do not commit: the optimizer's
            # ``active`` predicate holds LoRA, moments, and Adam's step
            # counter in the same pass (exactly like the loop engine)
            loss, lora_c, opt_c = lanes_step(params, lr, lora_c, opt_c, cl_mask, batch, sv, active)
            return (lora_c, opt_c), loss

        with jax.named_scope("client_train"):
            (cl_lora, cl_opt), losses = jax.lax.scan(
                step, (cl_lora, cl_opt), (batch_idx.T, step_valid.T)
            )
        return cl_lora, cl_opt, losses

    def train_lanes(params, lr, data, sample_valid, chosen, cl_lora, cl_opt, cl_mask,
                    lane_client, batch_idx, step_valid):
        # the cohort's rows, plus one scratch row per lane for a lane that
        # trains no client; each step gathers the rows its lanes name (never
        # one row twice), trains them, and scatters them back
        k, n_lanes = chosen.shape[0], lane_client.shape[0]

        def with_scratch(tree):
            return jax.tree.map(
                lambda x: jnp.concatenate([x, jnp.zeros((n_lanes,) + x.shape[1:], x.dtype)]),
                tree,
            )

        rows = jnp.concatenate([chosen, jnp.zeros((n_lanes,), chosen.dtype)])
        mask_c = with_scratch(cl_mask) if use_neuron_mask else None

        def step(carry, xs):
            lora_c, opt_c = carry
            lc, bidx, active = xs  # (L,) each
            pop = rows[lc]
            batch = {kk: v[pop, bidx] for kk, v in data.items()}
            sv = sample_valid[pop, bidx]
            mk = _gather(mask_c, lc) if use_neuron_mask else None
            loss, lo, op = lanes_step(
                params, lr, _gather(lora_c, lc), _gather(opt_c, lc), mk, batch, sv, active
            )
            lora_c = _scatter(lora_c, lc, lo, unique=True)
            return (lora_c, _scatter(opt_c, lc, op, unique=True)), loss

        with jax.named_scope("client_train"):
            (lora_c, opt_c), losses = jax.lax.scan(
                step,
                (with_scratch(cl_lora), with_scratch(cl_opt)),
                (lane_client.T, batch_idx.T, step_valid.T),
            )
            cohort = lambda tree: jax.tree.map(lambda x: x[:k], tree)  # noqa: E731
            return cohort(lora_c), cohort(opt_c), losses

    def body(params, global_lora, stacked_lora, stacked_opt, neuron_mask, gal_mask,
             data: Dict[str, Any], sample_valid, chosen, plan, weights, lr,
             stacked_residual=None, comp_mask=None):
        # named scopes (gather, merge_in, client_train, fedavg, scatter)
        # label the program's ops in a profile; they change no number
        with jax.named_scope("gather"):
            cl_lora = shard(_gather(stacked_lora, chosen))
            cl_opt = shard(_gather(stacked_opt, chosen))
            cl_mask = shard(_gather(neuron_mask, chosen)) if use_neuron_mask else None
            if hoist_client_data:
                data = shard({kk: v[chosen] for kk, v in data.items()})
                sample_valid = shard(sample_valid[chosen])

        # line 15: overwrite the GAL part of each client's LoRA with the
        # global copy; gal_mask leaves broadcast over the client axis. The
        # float blend must not silently widen bf16 leaves.
        with jax.named_scope("merge_in"):
            cl_lora = jax.tree.map(
                lambda g, l, m: (m * g + (1.0 - m) * l).astype(l.dtype),
                global_lora, cl_lora, gal_mask,
            )

        train = train_lanes if packed else train_cohort
        cl_lora, cl_opt, losses = train(
            params, lr, data, sample_valid, chosen, cl_lora, cl_opt, cl_mask, *plan
        )

        if compress is None:
            # line 18: weighted FedAvg fused over the GAL part only; with the
            # k axis sharded this contraction IS the server all-reduce (psum)
            with jax.named_scope("fedavg"):
                new_global = gal_weighted_merge(global_lora, gal_mask, cl_lora, weights)
            with jax.named_scope("scatter"):
                return (
                    new_global,
                    _scatter(stacked_lora, chosen, cl_lora),
                    _scatter(stacked_opt, chosen, cl_opt),
                    losses,
                )

        # compressed upload: each client ships the dequantized reconstruction
        # of its GAL delta (+ carried residual); the server applies the
        # reconstructions with the same normalized weights (sum 1), which
        # equals the value merge exactly when compression is lossless
        from repro.kernels import ops as _kops

        ef = compress["error_feedback"]

        def one(d, r, cm):
            return _kops.fake_compress(
                d, r, gal_mask if cm is None else cm,
                qmax=compress["qmax"],
                topk_ratio=compress["topk_ratio"],
                use_thresh=compress["use_thresh"],
            )

        with jax.named_scope("fedavg"):
            delta = jax.tree.map(
                lambda l, g, m: (l - g) * m, cl_lora, global_lora, gal_mask
            )
            cl_res = shard(_gather(stacked_residual, chosen)) if ef else None
            cl_cm = (
                shard(_gather(comp_mask, chosen)) if compress["has_comp_mask"] else None
            )
            y, new_res = jax.vmap(
                one,
                in_axes=(0, 0 if ef else None, 0 if cl_cm is not None else None),
            )(delta, cl_res, cl_cm)
            new_global = gal_delta_merge(global_lora, gal_mask, y, weights)

        with jax.named_scope("scatter"):
            return (
                new_global,
                _scatter(stacked_lora, chosen, cl_lora),
                _scatter(stacked_opt, chosen, cl_opt),
                losses,
                _scatter(stacked_residual, chosen, new_res) if ef else stacked_residual,
            )

    if packed:

        def packed_round_fn(params, global_lora, stacked_lora, stacked_opt, neuron_mask,
                            gal_mask, data, sample_valid, chosen, lane_client, batch_idx,
                            step_valid, weights, lr, stacked_residual=None, comp_mask=None):
            return body(
                params, global_lora, stacked_lora, stacked_opt, neuron_mask, gal_mask, data,
                sample_valid, chosen, (lane_client, batch_idx, step_valid), weights, lr,
                stacked_residual, comp_mask,
            )

        return packed_round_fn

    def round_fn(params, global_lora, stacked_lora, stacked_opt, neuron_mask, gal_mask,
                 data, sample_valid, chosen, batch_idx, step_valid, weights, lr,
                 stacked_residual=None, comp_mask=None):
        return body(
            params, global_lora, stacked_lora, stacked_opt, neuron_mask, gal_mask, data,
            sample_valid, chosen, (batch_idx, step_valid), weights, lr,
            stacked_residual, comp_mask,
        )

    return round_fn


def build_round_fn(
    loss_fn: Callable,
    opt_update: Callable,
    *,
    use_neuron_mask: bool,
    compress: Any = None,
    packed: bool = False,
    mesh: Any = None,
) -> Callable:
    """The jitted round program: one builder for every variant of
    :func:`_round_body`.

    Signature (leading client axis C on stacked trees, k chosen clients,
    S padded steps, NB padded batches of size B):

    ``round_fn(params, global_lora, stacked_lora, stacked_opt, neuron_mask,
    gal_mask, data, sample_valid, chosen, batch_idx, step_valid, weights, lr)
    -> (new_global_lora, new_stacked_lora, new_stacked_opt, losses (S, k))``

    ``neuron_mask`` is ignored (pass anything hashable-shaped, e.g. the
    stacked LoRA) when ``use_neuron_mask`` is False. A caller holding only
    the cohort (the out-of-core store) stacks it to a leading k axis and
    passes ``chosen = arange(k)``: the gather and scatter are the identity.

    ``compress`` (see :func:`_round_body`) adds two trailing arguments
    ``(stacked_residual, comp_mask)`` — ``jnp.zeros(())`` placeholders where
    ``error_feedback``/``has_comp_mask`` are off — and a fifth output, the
    updated stacked error-feedback residuals.

    ``packed`` trains the cohort in ``L < k`` lanes (``packed_round_fn``):
    ``lane_client``, ``batch_idx`` and ``step_valid`` (L, S) from
    :func:`repro.core.curriculum.pack_lanes` follow ``chosen``, and the
    losses come back (S, L). ``gather``, ``merge_in``, ``fedavg`` and
    ``scatter`` run over the cohort as unpacked; the ``client_train`` scan
    carries the cohort's LoRA and optimizer rows (plus L scratch rows) and
    at each step trains the L rows the lanes name. Every client trains the
    same batches in the same order from the same merged start, its Adam
    counter in its own row: only the schedule of lane-steps changes, so a
    lane no longer pads a short client to the longest one.

    ``mesh`` shards the program: the stacked client state, the data grid
    and the gathered cohort carry their leading client axis on the mesh's
    dp axes (the data grid gathered once per round); params, the global
    LoRA, the GAL mask and the step plan are replicated, and the fused
    FedAvg lowers to an all-reduce. The stack's client count C and the
    padded cohort k must be multiples of
    ``launch.mesh.num_client_groups(mesh)`` (the runner pads both). It
    keeps one lane per client, which the mesh shards, so ``packed`` with a
    mesh raises.

    The global LoRA and the stacked client state (LoRA, optimizer, and the
    residuals under ``compress``) are donated.
    """
    if packed and mesh is not None:
        raise ValueError("lane packing is single-device: a mesh shards one lane per client")
    donate = ("global_lora", "stacked_lora", "stacked_opt")
    if compress is not None:
        donate += ("stacked_residual",)
    body_kw, jit_kw = {}, {}
    if mesh is not None:
        client = client_sharding(mesh)
        repl = replicated_sharding(mesh)
        body_kw = dict(
            shard=lambda t: jax.lax.with_sharding_constraint(t, client),
            hoist_client_data=True,
        )
        in_shardings = (
            repl,  # params
            repl,  # global_lora
            client,  # stacked_lora
            client,  # stacked_opt
            client if use_neuron_mask else repl,  # neuron_mask
            repl,  # gal_mask
            client,  # data
            client,  # sample_valid
        ) + (repl,) * 5  # chosen, batch_idx, step_valid, weights, lr
        out_shardings = (repl, client, client, repl)
        if compress is not None:
            # scalar placeholders, where their knob is off, replicate
            res = client if compress["error_feedback"] else repl
            in_shardings += (res, client if compress["has_comp_mask"] else repl)
            out_shardings += (res,)
        jit_kw = dict(in_shardings=in_shardings, out_shardings=out_shardings)
    body = _round_body(
        loss_fn, opt_update, use_neuron_mask=use_neuron_mask, compress=compress,
        packed=packed, **body_kw,
    )
    return jax.jit(body, donate_argnames=donate, **jit_kw)


def _client_train_body(
    loss_fn: Callable, opt_update: Callable, *, use_neuron_mask: bool
) -> Callable:
    """One client's whole local round: merge-in (line 15) + step scan.

    The same ``make_client_step`` body as the vectorized round program, but
    scanned for a *single* client with no vmap barrier — the async engine
    dispatches one of these per completion event, so a fast client's program
    never waits on a straggler's (and has no lanes to pack).
    """
    client_step = make_client_step(loss_fn, opt_update)

    def train_fn(
        params,
        global_lora,
        lora,
        opt,
        neuron_mask,
        gal_mask,
        cdata: Dict[str, Any],
        sample_valid,
        batch_idx,
        step_valid,
        lr,
    ):
        # line 15: overwrite the GAL part with the pulled global version
        # (dtype-preserving: the float blend must not widen bf16 leaves)
        lora = jax.tree.map(
            lambda g, l, m: (m * g + (1.0 - m) * l).astype(l.dtype),
            global_lora, lora, gal_mask,
        )
        mask = neuron_mask if use_neuron_mask else None

        def step(carry, xs):
            lo, op = carry
            bidx, active = xs
            batch = {kk: v[bidx] for kk, v in cdata.items()}
            sv = sample_valid[bidx]
            # padded steps compute but do not commit (the optimizer's
            # ``active`` predicate — same no-op semantics as the vectorized
            # round program, no separate commit pass)
            loss, lo, op = client_step(params, lo, op, mask, batch, sv, lr, active)
            return (lo, op), loss

        (lora, opt), losses = jax.lax.scan(step, (lora, opt), (batch_idx, step_valid))
        return lora, opt, losses

    return train_fn


def build_client_train_fn(
    loss_fn: Callable, opt_update: Callable, *, use_neuron_mask: bool
) -> Callable:
    """Jitted single-client local round for the async engine.

    ``train_fn(params, global_lora, lora, opt, neuron_mask, gal_mask, cdata,
    sample_valid, batch_idx, step_valid, lr) -> (new_lora, new_opt,
    losses (S,))`` where ``cdata``/``sample_valid`` are one client's padded
    ``(NB, B, ...)`` data grid row and ``batch_idx``/``step_valid`` its
    ``(S,)`` curriculum step plan. The client's own LoRA/optimizer buffers
    are donated (a client is never dispatched while a previous update of its
    is still buffered); the pulled ``global_lora`` is NOT donated — several
    in-flight clients may share one version.
    """
    body = _client_train_body(loss_fn, opt_update, use_neuron_mask=use_neuron_mask)
    return jax.jit(body, donate_argnums=(2, 3))


def _over_clients(one: Callable, xs, chunk):
    """``one`` over the leading client axis of ``xs``: all clients at once
    (``vmap``), or ``chunk`` clients at a time (``lax.map``), so that an init
    program's temporaries hold a chunk's sequences, not every client's."""
    if chunk is None:
        return jax.vmap(one)(*xs)
    return jax.lax.map(lambda x: one(*x), xs, batch_size=chunk)


def _difficulty_body(loss_fn: Callable, metric: str, chunk=None) -> Callable:
    if metric == "fisher":

        def per_client(params, lora, cdata, csv):
            return fish.batch_fisher_scores(loss_fn, params, lora, cdata, csv)

    elif metric == "loss":
        masked = _masked_loss(loss_fn)

        def per_client(params, lora, cdata, csv):
            return jax.lax.map(
                lambda bm: masked(params, lora, *bm), (cdata, csv)
            )

    else:
        raise ValueError(f"no vectorized difficulty path for metric {metric!r}")

    @jax.named_scope("difficulty_grads")
    def diff(params, stacked_lora, data, sample_valid):
        # lora is mapped alongside the data: clients start from identical
        # copies, but a re-init after training must score each client's own
        # (trained, merged) LoRA exactly like the loop engine does
        return _over_clients(
            lambda lo, cd, cv: per_client(params, lo, cd, cv),
            (stacked_lora, data, sample_valid), chunk,
        )

    return diff


def build_difficulty_fn(loss_fn: Callable, metric: str, chunk=None) -> Callable:
    """Jitted (C, NB) difficulty scorer over the padded client stack.

    ``metric`` is "fisher" (Formula 17, via :func:`fisher.batch_fisher_scores`)
    or "loss" (masked mean inference loss). Host-side metrics (length, random)
    never hit the device and stay in the orchestrator. ``chunk`` scores that
    many clients at a time (same scores, fewer sequences in flight).
    """
    return jax.jit(_difficulty_body(loss_fn, metric, chunk))


def build_sharded_difficulty_fn(loss_fn: Callable, metric: str, mesh, chunk=None) -> Callable:
    """Difficulty scorer with each device scoring its shard of clients; the
    (C, NB) score grid is replicated on return (the host sorts it anyway)."""
    client = client_sharding(mesh)
    repl = replicated_sharding(mesh)
    return jax.jit(
        _difficulty_body(loss_fn, metric, chunk),
        in_shardings=(repl, client, client, client),
        out_shardings=repl,
    )


def _fim_warmup_body(loss_fn: Callable, momentum: float, chunk=None) -> Callable:
    def per_client(params, lora, cdata, csv):
        zero = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), lora)

        def body(carry, xs):
            fim, first = carry
            b, m = xs
            new = fish.fim_diag(loss_fn, params, lora, b, m)
            fim = jax.tree.map(
                lambda a, n: jnp.where(first, n, momentum * a + (1.0 - momentum) * n),
                fim,
                new,
            )
            return (fim, jnp.zeros((), bool)), None

        (fim, _), _ = jax.lax.scan(body, (zero, jnp.ones((), bool)), (cdata, csv))
        return fim

    @jax.named_scope("fim_warmup_program")
    def warm(params, stacked_lora, wdata, wsv):
        return _over_clients(
            lambda lo, cd, cv: per_client(params, lo, cd, cv), (stacked_lora, wdata, wsv), chunk
        )

    return warm


def build_fim_warmup_fn(loss_fn: Callable, momentum: float, chunk=None) -> Callable:
    """Jitted momentum-FIM warmup over all clients at once.

    ``warm(params, stacked_lora, wdata, wsv)`` with warmup batches stacked to
    ``(C, E, B, ...)`` returns the per-client momentum diag-FIM trees stacked
    to ``(C, ...)`` — a scan over the E warmup epochs of a vmap over clients,
    replaying ``fim_momentum_update`` (first epoch initializes, later epochs
    blend with momentum). ``chunk`` warms that many clients at a time.
    """
    return jax.jit(_fim_warmup_body(loss_fn, momentum, chunk))


def build_sharded_fim_warmup_fn(loss_fn: Callable, momentum: float, mesh, chunk=None) -> Callable:
    """FIM warmup with clients sharded over the mesh; the stacked FIM trees
    stay client-sharded (they feed the client-sharded neuron masks)."""
    client = client_sharding(mesh)
    repl = replicated_sharding(mesh)
    return jax.jit(
        _fim_warmup_body(loss_fn, momentum, chunk),
        in_shardings=(repl, client, client, client),
        out_shardings=client,
    )
