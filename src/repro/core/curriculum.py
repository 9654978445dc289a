"""Curriculum data selection (paper §4.2, Appendix C, Formulas 18-22).

Batches are sorted ascending by Fisher difficulty; round t uses the first
``B_k^t = clip(β + (1-β)·f(t)/(αT), β, 1) · n_batches`` of them. Strategies:
linear f(t)=t (paper's choice), sqrt, quadratic, exp (App. G.7), plus
``none`` (all data, no curriculum) and ``random`` (ablation G.2).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import numpy as np

STRATEGIES = ("linear", "sqrt", "quadratic", "exp", "none", "random")


@dataclasses.dataclass(frozen=True)
class CurriculumSchedule:
    strategy: str = "linear"
    beta: float = 0.6  # initial fraction of data
    alpha: float = 0.8  # fraction of rounds until all data is used
    total_rounds: int = 100

    def progress(self, t: int) -> float:
        """Ramp progress in [0, 1]: how far round ``t`` is through the
        curriculum's growth from the β-fraction to full data.

        0 at t=0, 1 once the ramp completes (t >= αT, or always for the
        ``none``/``random`` strategies, which start at full data). This is
        the signal the async engine's wall-clock-aware cohort sampling
        interpolates on (``AsyncAggConfig(sampling_bias=...)``): prefer
        fast clients while the ramp is young, go uniform once it is done.
        """
        if self.strategy in ("none", "random"):
            return 1.0
        denom = max(self.alpha * self.total_rounds, 1e-9)
        if self.strategy == "linear":
            prog = t / denom
        elif self.strategy == "sqrt":
            prog = math.sqrt(t) / math.sqrt(denom)
        elif self.strategy == "quadratic":
            prog = (t * t) / (denom * denom)
        elif self.strategy == "exp":
            prog = math.expm1(t) / max(math.expm1(denom), 1e-9)
        else:
            raise ValueError(self.strategy)
        return float(min(1.0, prog))

    def fraction(self, t: int) -> float:
        if self.strategy in ("none", "random"):
            return 1.0
        return float(
            min(1.0, self.beta + (1.0 - self.beta) * self.progress(t))
        )


def num_selected_batches(schedule: CurriculumSchedule, t: int, n_batches: int) -> int:
    return max(1, min(n_batches, int(round(schedule.fraction(t) * n_batches))))


def order_batches(
    difficulty_scores: np.ndarray, strategy: str = "linear", rng: Optional[np.random.Generator] = None
) -> np.ndarray:
    """Ascending-difficulty batch order (Alg. 1 line 5); random for ablation."""
    if strategy == "random":
        rng = rng or np.random.default_rng(0)
        return rng.permutation(len(difficulty_scores))
    return np.argsort(np.asarray(difficulty_scores), kind="stable")


def selected_batch_ids(
    schedule: CurriculumSchedule, t: int, order: np.ndarray
) -> np.ndarray:
    """Formula 19: batches with rank j < B_k^t are selected for round t."""
    count = num_selected_batches(schedule, t, len(order))
    return order[:count]


def step_plan(
    schedule: CurriculumSchedule,
    t: int,
    orders,
    local_epochs: int = 1,
    *,
    bucket: bool = True,
    max_selected=None,
):
    """Padded per-client step schedule for the vectorized/async engines.

    ``orders`` is the chosen clients' curriculum orders (ragged). Returns
    ``(batch_idx (k, S) int32, step_valid (k, S) f32)`` where
    ``S = local_epochs * padded_selected``: step ``s`` of client ``i`` trains
    on batch ``batch_idx[i, s]`` iff ``step_valid[i, s]``, replaying exactly
    the loop engine's epoch-major traversal of ``selected_batch_ids``. Padded
    steps keep index 0 and are masked to no-ops by the engine.

    With ``bucket`` (the default) the per-epoch selected count is rounded up
    to the next power of two (:func:`repro.data.pipeline.bucket_size`), so a
    full curriculum ramp from ``beta * NB`` to ``NB`` batches retraces the
    jitted round program at most ``log2(S_max) + 1`` times instead of once
    per distinct count — the padding steps are masked no-ops, so engine
    equivalence is unaffected. The single-device engine trains the valid
    steps in fewer lanes than clients where :func:`pack_lanes` fits them;
    its lane count depends on ``S`` alone (:func:`lane_count`), so a ramp
    compiles at most ``log2(S_max) + 1`` packed programs besides the
    unpacked ones.

    ``max_selected`` (optional, one entry per client, ``None`` entries =
    uncapped) caps each client's per-epoch selected count — the async
    engine's step-count adaptation: a capped client trains only the easiest
    ``max_selected[i]`` of its selected batches (curriculum order is a
    difficulty sort, so truncation keeps the prefix). Caps clamp to >= 1 and
    land in the same power-of-two buckets, so adaptation introduces no new
    retraces of the compiled per-client program.
    """
    from repro.data.pipeline import bucket_size

    sels = [selected_batch_ids(schedule, t, o) for o in orders]
    if max_selected is not None:
        sels = [
            s if cap is None else s[: max(1, int(cap))]
            for s, cap in zip(sels, max_selected)
        ]
    max_sel = max(len(s) for s in sels)
    padded = bucket_size(max_sel) if bucket else max_sel
    k, S = len(sels), local_epochs * padded
    batch_idx = np.zeros((k, S), np.int32)
    step_valid = np.zeros((k, S), np.float32)
    for i, sel in enumerate(sels):
        for e in range(local_epochs):
            lo = e * padded
            batch_idx[i, lo : lo + len(sel)] = sel
            step_valid[i, lo : lo + len(sel)] = 1.0
    return batch_idx, step_valid


def _first_fit_decreasing(counts, S: int, max_lanes: Optional[int] = None):
    """First-fit-decreasing placement of runs of ``counts[i]`` steps into
    lanes of ``S`` steps: ``(lane, offset)`` per run, and the lanes used.
    ``None`` where a run is longer than ``S`` or needs lane ``max_lanes``."""
    lanes, place = [], [None] * len(counts)
    # longest first; ties keep the cohort's order (a stable sort)
    for i in sorted(range(len(counts)), key=lambda i: -counts[i]):
        c = counts[i]
        if c > S:
            return None
        for lane, used in enumerate(lanes):
            if used + c <= S:
                break
        else:
            lane = len(lanes)
            if max_lanes is not None and lane >= max_lanes:
                return None
            lanes.append(0)
        place[i] = (lane, lanes[lane])
        lanes[lane] += c
    return place, len(lanes)


def lane_count(
    schedule: CurriculumSchedule, n_batches, k: int, S: int, local_epochs: int = 1
) -> int:
    """Lanes a packed round at scan length ``S`` gets: over every round of
    ``schedule``, the largest first-fit-decreasing lane count of the ``k``
    largest per-client step counts of the whole population (``n_batches``
    per client, ``local_epochs`` each) that fit in ``S``.

    It depends on ``S`` and the population alone, not on the round or the
    cohort drawn, so a job compiles at most one packed program per step
    bucket. A cohort that does not fit (first-fit-decreasing is not
    monotone) takes the unpacked program.
    """
    # the fraction only grows, and stays put once the ramp is done
    last = max(schedule.total_rounds, math.ceil(schedule.alpha * schedule.total_rounds))
    lanes, seen = 0, set()
    for t in range(last + 1):
        counts = tuple(local_epochs * num_selected_batches(schedule, t, n) for n in n_batches)
        if counts in seen:
            continue
        seen.add(counts)
        fits = sorted((c for c in counts if c <= S), reverse=True)
        lanes = max(lanes, _first_fit_decreasing(fits[:k], S)[1])
    return lanes


def pack_lanes(client_steps, S: int, L: int):
    """Pack a cohort's ragged step sequences into ``L`` lanes of ``S`` steps.

    ``client_steps[i]`` is chosen client ``i``'s batch ids in the order it
    trains them (:func:`step_plan`'s valid steps of row ``i``: epoch-major,
    each epoch the curriculum's selected batches). First-fit-decreasing puts
    each client's run whole into one lane at an offset, so a lane trains its
    clients one after another. Returns ``(lane_client (L, S) int32,
    batch_idx (L, S) int32, step_valid (L, S) f32)``: at step ``s`` lane
    ``l`` trains cohort row ``lane_client[l, s]`` on batch
    ``batch_idx[l, s]`` iff ``step_valid[l, s]``. A lane's steps after its
    last client stay inactive on that client's row; an empty lane points at
    the scratch row ``k + l`` (``k`` the cohort size), so no two lanes name
    one row at a step. ``None`` where the cohort does not fit.
    """
    k = len(client_steps)
    counts = [len(s) for s in client_steps]
    packed = _first_fit_decreasing(counts, S, L)
    if packed is None:
        return None
    place, _ = packed
    lane_client = np.repeat(np.arange(k, k + L, dtype=np.int32)[:, None], S, axis=1)
    batch_idx = np.zeros((L, S), np.int32)
    step_valid = np.zeros((L, S), np.float32)
    # by offset: each client's row runs from its first step to the lane's
    # end, until the lane's next client takes over
    for i in sorted(range(k), key=lambda i: place[i][1]):
        lane, lo = place[i]
        hi = lo + counts[i]
        lane_client[lane, lo:] = i
        batch_idx[lane, lo:hi] = client_steps[i]
        step_valid[lane, lo:hi] = 1.0
    return lane_client, batch_idx, step_valid
