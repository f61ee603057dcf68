"""Budget-law calibration: fit ``lam`` (and optionally ``hop_factor``) to a
recall target on a held-out query sample.

MCGI's Prop. 4.2 gives the *shape* of the per-query budget law
(L(q) ∝ exp(lam * LID(q))) but not its strength: ``lam`` trades mean I/O for
recall, and the right value is dataset geometry dependent. Following NSG's
treatment of its search parameter, the single knob is made transferable by
calibrating it against an operational recall target instead of hand-tuning
per dataset.

Monotonicity makes this a bisection, not a grid search: with the budget
center at the batch's LID center, ``lam = 0`` serves every query at the
geometric-mean budget, and raising ``lam`` spreads budgets apart —
below-average-LID queries shrink toward ``l_min`` (that's where the I/O
savings come from) while above-average ones grow toward ``l_max``. Measured
recall on a fixed sample is (noisily but reliably) monotone *non-increasing*
in ``lam``: the saturated hard queries gain little from the extra headroom,
while the shrunk easy lanes are where recall pressure appears. The
calibrated value is therefore the **largest** ``lam`` that still meets the
target — maximum budget-law savings subject to the recall SLO — found in
O(log(range/tol)) search evaluations. If even ``lam = lam_lo`` misses the
target, the hop budget (not the beam law) is the binding constraint:
``hop_factor`` is escalated and the bisection re-run.

Everything is deterministic under a fixed seed: the held-out sample, the
search engine, and the bisection path.

Beyond the single-knob fit, :func:`calibrate_budget_law_joint` fits
(lam, l_min) *jointly*: the budget floor l_min sets the law's geometric mid
(the real mean-I/O lever) and is exactly the recall pressure point the lam
bisection works around, so the joint pass scans candidate floors ascending
(max savings first) and runs the lam bisection at each until one meets the
target. The serving engine exposes both passes live via
``repro.serving.SearchEngine.recalibrate`` (the Online-MCGI refresh hook).

The distributed path goes one step further:
:func:`calibrate_budget_law_per_shard` runs the joint fit once *per shard*
on shard-local held-out queries (each shard's sub-graph has its own
geometry — one global law under-budgets the hard shards and over-budgets the
easy ones) and returns per-shard (lam, l_min) arrays that thread through
``ShardedIndexSpecs`` into the distributed step as runtime inputs — a
recalibration updates the arrays without recompiling anything.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax.numpy as jnp
import numpy as np

from repro.core import distance as distance_mod
from repro.core import search as search_mod


@dataclasses.dataclass(frozen=True)
class CalibrationResult:
    """Outcome of a budget-law calibration run.

    Attributes:
      lam:        fitted budget-law exponent — the largest value whose
                  measured recall still meets the target (max I/O savings
                  subject to the recall SLO).
      hop_factor: hop budget multiplier the fit succeeded at.
      recall:     measured recall at (lam, hop_factor) on the held-out sample.
      target:     the recall target that was requested.
      achieved:   whether ``recall >= target`` was reached inside the ranges.
      history:    every (lam, hop_factor, recall) evaluation, in order — the
                  measured recall curve the bisection walked.
      l_min:      fitted budget floor when the joint (lam, l_min) pass ran
                  (:func:`calibrate_budget_law_joint`); None for the plain
                  lam-only fit.
      joint_history: per-l_min-candidate summary of the joint pass —
                  (l_min, lam, hop_factor, recall, achieved) in evaluation
                  order; empty for the plain fit.
    """

    lam: float       # fitted exponent: largest value still meeting target
    hop_factor: int
    recall: float
    target: float
    achieved: bool
    history: tuple[tuple[float, int, float], ...]
    l_min: int | None = None
    joint_history: tuple[tuple[int, float, int, float, bool], ...] = ()

    def budget_cfg(
        self, base: search_mod.AdaptiveBeamBudget
    ) -> search_mod.AdaptiveBeamBudget:
        """The base config with the fitted knobs substituted in."""
        out = dataclasses.replace(
            base, lam=self.lam, hop_factor=self.hop_factor)
        if self.l_min is not None:
            out = dataclasses.replace(out, l_min=self.l_min)
        return out


def bisect_lam(
    eval_recall: Callable[[float], float],
    target: float,
    lam_lo: float = 0.0,
    lam_hi: float = 1.0,
    tol: float = 0.02,
    max_iters: int = 8,
) -> tuple[float, float, list[tuple[float, float]]]:
    """Largest ``lam`` in [lam_lo, lam_hi] with ``eval_recall(lam) >= target``.

    Assumes ``eval_recall`` is monotone non-increasing in ``lam`` (see module
    docstring): the bisection keeps a feasible lower end and pushes it up.
    Returns (lam, recall_at_lam, [(lam, recall) evaluations]). When even
    ``lam_lo`` misses the target, returns (lam_lo, recall_at_lo, history) —
    the caller decides whether to escalate another knob (hop_factor).
    """
    history: list[tuple[float, float]] = []

    def f(lam: float) -> float:
        r = float(eval_recall(float(lam)))
        history.append((float(lam), r))
        return r

    r_lo = f(lam_lo)
    if r_lo < target:
        return lam_lo, r_lo, history
    r_hi = f(lam_hi)
    if r_hi >= target:
        return lam_hi, r_hi, history
    lo, hi, r_at_lo = lam_lo, lam_hi, r_lo
    for _ in range(max_iters):
        if hi - lo <= tol:
            break
        mid = 0.5 * (lo + hi)
        r_mid = f(mid)
        if r_mid >= target:
            lo, r_at_lo = mid, r_mid
        else:
            hi = mid
    return lo, r_at_lo, history


def holdout_sample(
    n_queries: int, sample: int, seed: int = 0
) -> np.ndarray:
    """Deterministic held-out query subset (sorted indices for stable
    gather order — bit-reproducible recall measurements)."""
    sample = min(sample, n_queries)
    rng = np.random.default_rng(seed)
    sel = rng.choice(n_queries, size=sample, replace=False)
    return np.sort(sel)


def calibrate_budget_law(
    eval_recall: Callable[[search_mod.AdaptiveBeamBudget], float],
    base_cfg: search_mod.AdaptiveBeamBudget,
    recall_target: float,
    *,
    lam_range: tuple[float, float] = (0.0, 1.0),
    max_hop_factor: int = 16,
    tol: float = 0.02,
    max_iters: int = 8,
) -> CalibrationResult:
    """Fit ``lam`` (escalating ``hop_factor`` when needed) to ``recall_target``.

    ``eval_recall`` measures recall of one candidate config on the held-out
    sample (see :func:`exact_recall_eval` / :func:`tiered_recall_eval`).
    ``hop_factor`` doubles from ``base_cfg.hop_factor`` up to
    ``max_hop_factor`` whenever even ``lam = lam_range[0]`` misses the
    target (the hop budget, not the beam law, is binding there).
    """
    history: list[tuple[float, int, float]] = []
    hop_factor = base_cfg.hop_factor
    while True:
        cfg_at = dataclasses.replace(base_cfg, hop_factor=hop_factor)

        def eval_lam(lam: float, _cfg=cfg_at) -> float:
            return eval_recall(dataclasses.replace(_cfg, lam=lam))

        lam, recall, lam_hist = bisect_lam(
            eval_lam, recall_target, lam_range[0], lam_range[1],
            tol=tol, max_iters=max_iters)
        history.extend((lm, hop_factor, r) for lm, r in lam_hist)
        if recall >= recall_target or hop_factor * 2 > max_hop_factor:
            return CalibrationResult(
                lam=float(lam), hop_factor=int(hop_factor),
                recall=float(recall), target=float(recall_target),
                achieved=bool(recall >= recall_target),
                history=tuple(history))
        hop_factor *= 2


def joint_l_min_candidates(
    base_cfg: search_mod.AdaptiveBeamBudget, floor: int = 2
) -> tuple[int, ...]:
    """Default l_min grid for the joint fit: halving down from the base
    config's floor to ``floor``, returned ascending (max-savings first)."""
    cands = [int(base_cfg.l_min)]
    while cands[-1] // 2 >= max(1, floor):
        cands.append(cands[-1] // 2)
    return tuple(sorted(set(cands)))


def calibrate_budget_law_joint(
    make_eval: Callable[
        [search_mod.AdaptiveBeamBudget],
        Callable[[search_mod.AdaptiveBeamBudget], float]],
    base_cfg: search_mod.AdaptiveBeamBudget,
    recall_target: float,
    *,
    l_min_candidates: tuple[int, ...] | None = None,
    lam_range: tuple[float, float] = (0.0, 1.0),
    max_hop_factor: int = 16,
    tol: float = 0.02,
    max_iters: int = 8,
) -> CalibrationResult:
    """Joint (lam, l_min) fit: the smallest feasible budget floor, then the
    largest feasible lam at that floor.

    ``l_min`` is the recall pressure point the lam bisection works around:
    the budget law centers at the geometric mid ``sqrt(l_min * l_max)``, so
    lowering ``l_min`` lowers *every* query's expected budget (the real I/O
    lever), while recall pressure concentrates on the easy lanes shrunk
    toward the floor. Feasibility is monotone in ``l_min`` (raising the floor
    only widens frontiers), so the joint fit scans the candidate floors
    *ascending* and returns the first whose lam bisection
    (:func:`calibrate_budget_law`, hop_factor escalation included) meets the
    target — maximum savings subject to the recall SLO. If no floor is
    feasible the largest candidate's (best-recall) fit is returned with
    ``achieved=False``.

    ``make_eval`` builds a recall evaluator *specialised to one candidate's
    shape knobs* — the shared-probe evaluators
    (:func:`exact_recall_eval` / :func:`tiered_recall_eval` with
    ``base_cfg=``) compile one probe per l_min candidate and reuse it across
    that candidate's whole lam bisection. Deterministic end to end under a
    fixed seed, like the plain fit.
    """
    if l_min_candidates is None:
        l_min_candidates = joint_l_min_candidates(base_cfg)
    cands = sorted({int(c) for c in l_min_candidates})
    assert cands and 0 < cands[0] and cands[-1] <= base_cfg.l_max, cands
    joint_hist: list[tuple[int, float, int, float, bool]] = []
    last: CalibrationResult | None = None
    for lm in cands:
        cfg_lm = dataclasses.replace(base_cfg, l_min=lm)
        result = calibrate_budget_law(
            make_eval(cfg_lm), cfg_lm, recall_target, lam_range=lam_range,
            max_hop_factor=max_hop_factor, tol=tol, max_iters=max_iters)
        joint_hist.append((lm, result.lam, result.hop_factor, result.recall,
                           result.achieved))
        last = result
        if result.achieved:
            return dataclasses.replace(
                result, l_min=lm, joint_history=tuple(joint_hist))
    assert last is not None
    return dataclasses.replace(
        last, l_min=cands[-1], joint_history=tuple(joint_hist))


@dataclasses.dataclass(frozen=True)
class ShardCalibration:
    """Per-shard budget laws fitted by :func:`calibrate_budget_law_per_shard`.

    Attributes:
      lam / l_min / hop_factor: the fitted knobs, one entry per shard.
      results: each shard's full :class:`CalibrationResult` (histories,
        achieved flags) in shard order.
    """

    lam: tuple[float, ...]
    l_min: tuple[int, ...]
    hop_factor: tuple[int, ...]
    results: tuple[CalibrationResult, ...]

    @property
    def achieved(self) -> bool:
        return all(r.achieved for r in self.results)

    def law_arrays(self) -> tuple[np.ndarray, np.ndarray]:
        """The (lam (S,) f32, l_min (S,) i32) runtime arrays the distributed
        step consumes (``shard_laws=`` on the backend / ``per_shard_laws``
        steps; serialized via ``repro.index.save_index(shard_laws=)``).

        Deploy together with :meth:`serving_budget` — ``hop_factor`` is not
        a per-shard runtime array, so a fit that escalated it on any shard
        must raise the serving config's global value to match."""
        return (np.asarray(self.lam, np.float32),
                np.asarray(self.l_min, np.int32))

    def serving_budget(
        self, base: search_mod.AdaptiveBeamBudget
    ) -> search_mod.AdaptiveBeamBudget:
        """``base`` with ``hop_factor`` escalated to the per-shard max.

        The distributed step derives hop deadlines from the (global) budget
        config's ``hop_factor``; a shard whose fit only met the target after
        hop-factor escalation would silently serve under a tighter deadline
        than it was calibrated to. Hop limits are *caps*, so serving the
        largest fitted escalation everywhere never tightens any shard's fit
        (easy shards still retire when their frontier closes)."""
        return dataclasses.replace(base, hop_factor=max(self.hop_factor))


def calibrate_budget_law_per_shard(
    make_shard_eval: Callable[[int], Callable],
    base_cfg: search_mod.AdaptiveBeamBudget,
    recall_target: float,
    n_shards: int,
    *,
    joint: bool = True,
    **fit_kw,
) -> ShardCalibration:
    """Fit one budget law per shard of a distributed index.

    ``make_shard_eval(s)`` returns shard ``s``'s evaluator *factory* (the
    ``make_eval`` shape of :func:`calibrate_budget_law_joint`: config ->
    recall evaluator on shard-local held-out queries — see
    :func:`shard_exact_recall_evals`). Each shard runs the joint
    (lam, l_min) fit (or the plain lam fit with ``joint=False``) against the
    same ``recall_target``: the global merge only ever *adds* candidates
    across shards, so per-shard recall at the target is a sound (mildly
    conservative) surrogate for global recall at the target.

    Deterministic end to end under a fixed seed, shard by shard. Returns a
    :class:`ShardCalibration`; its :meth:`~ShardCalibration.law_arrays` feed
    ``DistributedBackend(shard_laws=)`` directly.
    """
    results = []
    for s in range(n_shards):
        factory = make_shard_eval(s)
        if joint:
            r = calibrate_budget_law_joint(
                factory, base_cfg, recall_target, **fit_kw)
        else:
            r = calibrate_budget_law(
                factory(base_cfg), base_cfg, recall_target, **fit_kw)
        results.append(r)
    return ShardCalibration(
        lam=tuple(float(r.lam) for r in results),
        l_min=tuple(int(r.l_min if r.l_min is not None else base_cfg.l_min)
                    for r in results),
        hop_factor=tuple(int(r.hop_factor) for r in results),
        results=tuple(results),
    )


def calibrate_budget_law_per_class(
    make_eval: Callable[
        [search_mod.AdaptiveBeamBudget],
        Callable[[search_mod.AdaptiveBeamBudget], float]],
    base_cfg: search_mod.AdaptiveBeamBudget,
    recall_targets: "dict[str, float]",
    *,
    joint: bool = True,
    **fit_kw,
) -> "dict[str, CalibrationResult]":
    """Fit one budget law per QoS class — the serving front door's knob.

    ``recall_targets`` maps class name -> recall target (e.g.
    ``{"interactive": 0.85, "batch": 0.97}``); each class runs the joint
    (lam, l_min) fit (or the plain lam fit with ``joint=False``) against
    *its own* target over the same ``make_eval`` factory and the same
    held-out sample.  The result is the per-class (lam, l_min) split the
    paper's budget law makes free: a looser target fits a higher lam and a
    lower floor — fewer slow-tier reads — while a stricter class keeps its
    recall SLO, on the same index and the same backend.

    Deploy via :func:`class_budget_cfgs`: one
    :class:`~repro.serving.engine.SearchEngine` per class over one shared
    backend, handed to ``repro.serving.server.FrontDoor`` keyed by class
    name.  Deterministic end to end under a fixed seed, class by class
    (dict order is preserved).
    """
    out: dict[str, CalibrationResult] = {}
    for name, target in recall_targets.items():
        if joint:
            out[name] = calibrate_budget_law_joint(
                make_eval, base_cfg, float(target), **fit_kw)
        else:
            out[name] = calibrate_budget_law(
                make_eval(base_cfg), base_cfg, float(target), **fit_kw)
    return out


def class_budget_cfgs(
    results: "dict[str, CalibrationResult]",
    base_cfg: search_mod.AdaptiveBeamBudget,
) -> "dict[str, search_mod.AdaptiveBeamBudget]":
    """Per-class serving configs from a :func:`calibrate_budget_law_per_class`
    fit — each class's base config with its fitted knobs substituted in."""
    return {name: r.budget_cfg(base_cfg) for name, r in results.items()}


def shard_exact_recall_evals(
    vectors, adj, entries, queries, n_shards: int, *,
    k: int = 10, sample: int = 256, seed: int = 0,
) -> Callable[[int], Callable]:
    """``make_shard_eval`` over a shard-major distributed layout.

    ``vectors``/``adj`` are the concatenated shard-major arrays (shard s owns
    rows [s*per, (s+1)*per) with shard-local adjacency ids — the layout
    ``make_distributed_search`` requires, *before* device_put); ``entries``
    the (n_shards, E) entry candidates, each query starting from its
    nearest as in serving. Shard recall is measured against the
    shard's own brute-force top-k: the budget law governs the shard-local
    walk, and the global merge sits outside it. The held-out sample is drawn
    once per shard from the same seed, so every shard calibrates against the
    same queries.
    """
    per = adj.shape[0] // n_shards

    def make_shard_eval(s: int) -> Callable:
        x_s = vectors[s * per:(s + 1) * per]
        adj_s = adj[s * per:(s + 1) * per]
        entry_s = search_mod.nearest_entries(
            jnp.asarray(entries)[s], jnp.asarray(x_s), jnp.asarray(queries))
        _, gt_s = distance_mod.brute_force_topk(
            jnp.asarray(queries), jnp.asarray(x_s), k=k)

        def factory(cfg: search_mod.AdaptiveBeamBudget) -> Callable:
            return exact_recall_eval(
                x_s, adj_s, entry_s, queries, gt_s, k=k, sample=sample,
                seed=seed, base_cfg=cfg)

        return factory

    return make_shard_eval


def _candidate_grants(cfg: search_mod.AdaptiveBeamBudget, q_lid):
    """Budgets + hop limits for one candidate config, from a shared probe's
    LID estimates — plain traced arithmetic, no recompilation per candidate
    (the jitted probe/continue programs are keyed on the *base* config only;
    lam / hop_factor / center never enter a static argument here)."""
    from repro.core import mapping as mapping_mod

    center = None if cfg.center is None else jnp.float32(cfg.center)
    budgets = mapping_mod.adaptive_beam_budget(
        q_lid, cfg.lam, cfg.l_min, cfg.l_max, mu=center)
    return budgets, search_mod._bucket_hop_limits(cfg, budgets, None)


def _check_shape_knobs(cfg, base):
    """The shared probe state is only valid while the shape knobs match —
    the calibration loop varies lam/hop_factor/center exclusively."""
    same = (cfg.l_min == base.l_min and cfg.l_max == base.l_max
            and cfg.probe_hops == base.probe_hops
            and cfg.lid_k == base.lid_k)
    if not same:
        raise ValueError(
            f"calibration evaluator is specialised to probe knobs of {base}; "
            f"got {cfg}")


def exact_recall_eval(
    x, adj, entry, queries, gt_ids, *, k: int = 10,
    sample: int = 256, seed: int = 0,
    base_cfg: search_mod.AdaptiveBeamBudget | None = None,
) -> Callable[[search_mod.AdaptiveBeamBudget], float]:
    """Recall evaluator over the exact-distance adaptive engine.

    Draws a deterministic held-out sample of ``queries`` (with matching
    ground-truth rows) once. The probe walk depends only on the shape knobs
    (l_min/l_max/probe_hops/lid_k), never on lam or hop_factor, so it runs
    *once*, lazily, at the first evaluation; each candidate then re-runs only
    the continue phase with its own (traced) budgets and hop limits — the
    whole bisection shares two compiled programs.  ``entry`` is one node
    for every query, or one per row of ``queries``.
    """
    sel = holdout_sample(queries.shape[0], sample, seed)
    q_s, gt_s = queries[sel], gt_ids[sel][:, :k]
    if jnp.ndim(entry):
        entry = jnp.asarray(entry)[sel]
    probe = {}

    def eval_recall(cfg: search_mod.AdaptiveBeamBudget) -> float:
        if not probe:
            probe["base"] = base_cfg or cfg
            probe["state"], _, _, probe["q_lid"] = search_mod._probe_exact_jit(
                x, adj, q_s, entry, probe["base"])
        _check_shape_knobs(cfg, probe["base"])
        budgets, hop_limits = _candidate_grants(cfg, probe["q_lid"])
        beam_ids, _, _, _ = search_mod._continue_exact_jit(
            x, adj, probe["state"], q_s, budgets, hop_limits,
            budget_cfg=probe["base"])
        return float(distance_mod.recall_at_k(beam_ids[:, :k], gt_s))

    return eval_recall


def tiered_recall_eval(
    index, queries, gt_ids, *, k: int = 10, sample: int = 256, seed: int = 0,
    base_cfg: search_mod.AdaptiveBeamBudget | None = None,
) -> Callable[[search_mod.AdaptiveBeamBudget], float]:
    """Recall evaluator over the deployed two-tier path: PQ-routed walk +
    slow-tier rerank, so the fitted lam reflects ADC distance noise too.
    Same shared-probe structure as :func:`exact_recall_eval` — one probe, one
    continue program, no per-candidate recompilation."""
    from repro.index.disk import _query_luts

    sel = holdout_sample(queries.shape[0], sample, seed)
    q_s, gt_s = queries[sel], gt_ids[sel][:, :k]
    luts = _query_luts(index, q_s)
    probe = {}

    def eval_recall(cfg: search_mod.AdaptiveBeamBudget) -> float:
        if not probe:
            probe["base"] = base_cfg or cfg
            probe["state"], _, _, probe["q_lid"] = search_mod._probe_pq_jit(
                index.codes, index.graph.adj, luts, index.graph.entry,
                probe["base"])
        _check_shape_knobs(cfg, probe["base"])
        budgets, hop_limits = _candidate_grants(cfg, probe["q_lid"])
        beam_ids, _, _, _ = search_mod._continue_pq_jit(
            index.codes, index.graph.adj, probe["state"], luts, budgets,
            hop_limits, budget_cfg=probe["base"])
        ids, _ = search_mod._rerank_slow_tier_jit(
            beam_ids, index.vectors, q_s, k=k)
        return float(distance_mod.recall_at_k(ids, gt_s))

    return eval_recall
