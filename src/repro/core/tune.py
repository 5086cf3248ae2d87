"""Per-plan autotuning for the ``pallas-tree`` backend (DESIGN.md §9).

One fixed (LANE_TILE=128, K=4) kernel configuration cannot cover every
query-density regime: the committed ``BENCH_traversal.json`` trajectory
showed it losing wall clock to the reference engine on two of three
scenarios, because a lane tile retires only when its *slowest* lane
finishes and the unroll factor K multiplies tail waste in sparse-frontier
phases. This module picks, per clustering plan and per phase
(``first_pass`` / ``sweep`` / ``border``):

  * the **execution engine** — the lane-tiled Pallas kernel, or the
    vmapped reference engine for shapes where kernel launch overhead
    dominates (tiny compacted frontiers, small border sets);
  * the **lane tile** and **unroll factor K** from the candidate grid
    (:data:`TUNE_LANE_TILES` × :data:`TUNE_UNROLLS`), subject to the
    VMEM budget (lane state + whole-array index residency must fit);
  * the **lane reordering policy** (``repro.core.traversal.lane_sort_key``)
    — Morton order for external batches, measured walk-depth order for
    resident queries once the fused first pass has calibrated a
    per-query depth oracle (``Trace.iters`` is free: the kernel already
    returns it).

Every choice changes only the *schedule*; results are bit-identical by
construction (the kernel shares ``make_step`` with the reference engine
and inverse-permutes reordered lanes on exit), so the tuner needs no
conformance machinery of its own — ``tests/test_tune.py`` pins the full
config grid byte-equal to the golden fixtures.

Modes (``REPRO_TUNE`` environment variable):

  * ``off``        — the deterministic pin: every phase runs the Pallas
    kernel at (128, 4) with no reordering, reproducing the pre-tuner
    behavior exactly (golden tests, counter gates).
  * ``heuristic``  — the default: a stats-driven config (no measurement)
    derived from the backend and cheap index stats; includes the
    depth-rank calibration and the small-frontier reference fallback.
  * ``search``     — measured per-phase A/B over the candidate grid on
    the actual workload shapes; cached in the dispatcher's plan LRU
    under :func:`stats_key` so equal-shaped plans reuse the result.
    This is what ``make bench-tune`` runs.

The chosen config is recorded in the obs metrics snapshot (gauge
``tuned_config_info`` with per-phase labels) and in
``BENCH_traversal.json`` as ``tuned_config``.
"""
from __future__ import annotations

import os
import time
from functools import partial
from typing import Any, NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from . import traversal

#: Candidate grid. The conformance test sweeps all of it; the measured
#: search uses the subset in _SEARCH_LANE_TILES/_SEARCH_UNROLLS.
TUNE_LANE_TILES = (64, 128, 256, 512)
TUNE_UNROLLS = (1, 2, 4, 8)

_SEARCH_LANE_TILES = (128, 256, 512)
_SEARCH_UNROLLS = (1, 4)

#: VMEM budget for whole-array index residency + per-lane walk state:
#: beyond this the kernel would spill, so candidate lane tiles are capped.
VMEM_BUDGET_BYTES = 8 << 20

#: Per-lane walk state footprint (node/ptr/carry/evals/iters + query
#: coords), conservative upper bound in bytes.
_LANE_STATE_BYTES = 64


class PhaseConfig(NamedTuple):
    """How one clustering phase executes its traversals."""
    engine: str = "pallas"      # "pallas" | "reference" | "auto"
    lane_tile: int = 128
    unroll: int = 4
    reorder: str = "none"       # "none" | "morton" | "depth"


class TunedConfig(NamedTuple):
    """A full per-plan tuning decision (one PhaseConfig per phase).

    ``min_lanes``: Pallas phases whose (padded) lane count falls below
    this run the reference engine instead — compacted-frontier sweeps
    shrink to a few dozen lanes where kernel launch overhead loses.
    ``border_min_frac``: an ``engine="auto"`` border phase picks the
    kernel only when the non-core fraction reaches this (noise-heavy
    datasets traverse nearly all lanes; clean ones a small minority).
    """
    first_pass: PhaseConfig = PhaseConfig()
    sweep: PhaseConfig = PhaseConfig()
    border: PhaseConfig = PhaseConfig()
    min_lanes: int = 0
    border_min_frac: float = 0.0
    source: str = "pinned"


#: REPRO_TUNE=off — today's fixed configuration, bit-and-schedule
#: identical to the pre-tuner kernel path.
PINNED = TunedConfig()


def mode() -> str:
    """Resolve the REPRO_TUNE environment variable to a tuner mode."""
    m = os.environ.get("REPRO_TUNE", "").strip().lower()
    if m in ("off", "0", "none", "pinned"):
        return "off"
    if m == "search":
        return "search"
    return "heuristic"


# ---------------------------------------------------------------------------
# Engine resolution: stable function identities per PhaseConfig
# ---------------------------------------------------------------------------

_ENGINE_FNS: dict[PhaseConfig, Any] = {}


def engine_fn(cfg: PhaseConfig):
    """The traversal engine callable for ``cfg``, with a *stable identity*.

    ``_fused_first_pass_jit`` takes the engine as a static jit argument,
    so the same PhaseConfig must always resolve to the same function
    object or every call would retrace. The default (128, 4, none)
    kernel config resolves to the bare ``repro.kernels.traverse.traverse``
    — the exact object the pre-tuner path used — so REPRO_TUNE=off hits
    the same jit cache entries as before the tuner existed.
    """
    if cfg.engine == "reference":
        return traversal.traverse
    fn = _ENGINE_FNS.get(cfg)
    if fn is None:
        from repro.kernels import traverse as pallas_traverse
        if (cfg.lane_tile == pallas_traverse.LANE_TILE
                and cfg.unroll == pallas_traverse.PALLAS_UNROLL
                and cfg.reorder == "none"):
            fn = pallas_traverse.traverse
        else:
            fn = partial(pallas_traverse.traverse, lane_tile=cfg.lane_tile,
                         unroll=cfg.unroll, reorder=cfg.reorder)
        _ENGINE_FNS[cfg] = fn
    return fn


def lane_tiles_within_budget(index_bytes: int,
                             candidates=TUNE_LANE_TILES) -> tuple:
    """Candidate lane tiles whose state + index fit the VMEM budget."""
    fit = tuple(t for t in candidates
                if index_bytes + t * _LANE_STATE_BYTES <= VMEM_BUDGET_BYTES)
    return fit or candidates[:1]


# ---------------------------------------------------------------------------
# Per-plan state
# ---------------------------------------------------------------------------

class TuneState:
    """Mutable tuning state attached to a dispatcher Plan.

    Holds the (immutable) :class:`TunedConfig` plus the lazily-calibrated
    depth oracle: after the first fused pass, ``calibrate`` stores that
    pass's per-query loop-trip counts (``Trace.iters``, indexed by sorted
    point id), and subsequent ``reorder="depth"`` traversals sort lanes
    by descending depth. The oracle only affects lane *order* (results
    are inverse-permuted), so a stale or missing oracle is a performance
    detail, never a correctness one.
    """

    def __init__(self, config: TunedConfig):
        self.config = config
        self.depth_rank = None
        self.info: dict = {}

    def phase(self, name: str, *, n_lanes: int | None = None,
              n: int | None = None) -> PhaseConfig:
        """Resolve the phase's config against the actual lane shape."""
        cfg: PhaseConfig = getattr(self.config, name)
        if cfg.engine == "auto":
            frac = 1.0 if not n else (n_lanes or 0) / n
            cfg = cfg._replace(
                engine="pallas" if frac >= self.config.border_min_frac
                else "reference")
        if (cfg.engine == "pallas" and n_lanes is not None
                and n_lanes < self.config.min_lanes):
            cfg = cfg._replace(engine="reference")
        return cfg

    def rank_for(self, cfg: PhaseConfig):
        """The depth oracle, iff this phase's kernel wants it."""
        if cfg.engine == "pallas" and cfg.reorder == "depth":
            return self.depth_rank
        return None

    def calibrate(self, iters) -> None:
        """Store the fused pass's per-query walk depth as the oracle."""
        if self.depth_rank is None and self.config.source != "pinned":
            self.depth_rank = iters

    def describe(self) -> dict:
        """JSON-safe record of the decision (bench artifact, obs gauge)."""
        out = {"source": self.config.source,
               "min_lanes": int(self.config.min_lanes),
               "border_min_frac": float(self.config.border_min_frac),
               "calibrated": self.depth_rank is not None}
        for name in ("first_pass", "sweep", "border"):
            cfg: PhaseConfig = getattr(self.config, name)
            out[name] = {"engine": cfg.engine,
                         "lane_tile": int(cfg.lane_tile),
                         "unroll": int(cfg.unroll),
                         "reorder": cfg.reorder}
        out.update(self.info)
        return out


# ---------------------------------------------------------------------------
# Config derivation: stats key, heuristic, measured search
# ---------------------------------------------------------------------------

def _index_bytes(segs, tree) -> int:
    """Whole-array VMEM footprint of the (segments, tree) index."""
    total = 0
    for holder in (segs, tree):
        if holder is None:
            continue
        for leaf in holder:
            if leaf is not None and hasattr(leaf, "nbytes"):
                total += int(leaf.nbytes)
    return total


def stats_key(segs, eps: float, min_pts: int) -> tuple:
    """Cheap index stats bucketed into a search-cache key.

    Log2-bucketed (n, leaf occupancy, eps-cell density estimate) plus the
    dimension: plans with the same bucket tuple share a measured config.
    The density estimate here is grid-based (occupied eps-cells), cheap
    enough to compute *before* any traversal; the measured search refines
    it with the fused count pass's mean hit count and records both in the
    tuner artifact.
    """
    n = int(segs.n_points)
    m = max(int(segs.n_segments), 1)
    d = int(segs.pts.shape[1])
    occupancy = n / m
    density = occupancy
    if eps > 0:
        from . import fdbscan
        keys = fdbscan._cell_keys(segs.pts, eps)
        density = n / max(len(np.unique(keys)), 1)

    def bucket(x: float) -> int:
        return int(round(np.log2(max(x, 1.0))))

    return (d, bucket(n), bucket(occupancy + 1), bucket(density + 1),
            int(min_pts))


def heuristic(segs, tree) -> TunedConfig:
    """Stats-driven config, no measurement.

    The kernel runs only in interpret mode (it has no compiled TPU form,
    ``repro.kernels.traverse.TPU_UNSUPPORTED``), where per-trip Python
    overhead dominates: the widest in-budget lane tile with K=1 minimizes
    trips, and measured phase costs (BENCH_traversal.json) show the
    reference engine winning small compacted batches — hence the
    fallbacks.
    """
    wide = max(lane_tiles_within_budget(_index_bytes(segs, tree)))
    fp = PhaseConfig("pallas", wide, 1, "depth")
    sw = PhaseConfig("pallas", wide, 1, "depth")
    bd = PhaseConfig("auto", min(256, wide), 1, "none")
    return TunedConfig(first_pass=fp, sweep=sw, border=bd,
                       min_lanes=256, border_min_frac=0.9,
                       source="heuristic")


def _time_best(fn, repeats: int = 3) -> float:
    """Best-of-N wall time after a compile/warmup call."""
    fn()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def search(segs, tree, eps: float, min_pts: int
           ) -> tuple[TunedConfig, dict]:
    """Measured per-phase A/B over the candidate grid.

    Runs the fused first pass once with the reference engine to obtain
    the workload's real phase shapes (core mask, first-sweep frontier,
    border set) and the depth oracle, then times each candidate engine on
    those exact shapes and keeps the per-phase winner. All candidates
    produce bit-identical results, so this is purely a schedule decision;
    the caller (dispatch) caches the returned ``(config, info)`` under
    :func:`stats_key` — the config is shareable across equal-shaped
    plans, while the per-plan depth oracle is recalibrated by each plan's
    own first pass (it is indexed by that plan's sorted point ids).
    """
    from . import fdbscan

    base = heuristic(segs, tree)
    tiles = lane_tiles_within_budget(_index_bytes(segs, tree),
                                     _SEARCH_LANE_TILES)
    info: dict = {}

    core, labels0, vals0, absorbed, first = fdbscan._fused_first_pass(
        tree, segs, eps, min_pts)
    jax.block_until_ready(core)
    rank = first.iters
    info["mean_hits"] = float(jnp.mean(first.hits))

    def candidates(reorder: str):
        yield PhaseConfig("reference", 0, 0, "none")
        for lt in tiles:
            for k in _SEARCH_UNROLLS:
                yield PhaseConfig("pallas", lt, k, reorder)

    def pick(reorder: str, run) -> tuple[PhaseConfig, dict]:
        timings = {}
        for cand in candidates(reorder):
            fn = engine_fn(cand)
            kw = ({"depth_rank": rank}
                  if cand.engine == "pallas" and cand.reorder == "depth"
                  else {})
            label = (cand.engine if cand.engine == "reference" else
                     f"pallas/{cand.lane_tile}x{cand.unroll}/{cand.reorder}")
            timings[label] = _time_best(lambda: run(fn, kw))
        best_label = min(timings, key=timings.get)
        best = next(c for c in candidates(reorder)
                    if (c.engine if c.engine == "reference" else
                        f"pallas/{c.lane_tile}x{c.unroll}/{c.reorder}"
                        ) == best_label)
        return best, timings

    # -- first pass: the full fused count+minlabel walk ------------------
    def run_first(fn, kw):
        out = fdbscan._fused_first_pass(tree, segs, eps, min_pts,
                                        traverse_fn=fn, **kw)
        jax.block_until_ready(out[0])

    fp, t_fp = pick("depth", run_first)

    # -- sweep: the first (widest) min-label sweep shape -----------------
    core_np = np.asarray(core)
    ids_sweep = fdbscan._compact_ids(core_np)
    nm_core = fdbscan._frontier_node_mask(tree, segs, core)

    def run_sweep(fn, kw):
        tr = fn(tree, segs,
                traversal.intersects(traversal.sphere(eps), ids=ids_sweep),
                traversal.MinLabelVisitor(labels0, core),
                node_mask=nm_core, **kw)
        jax.block_until_ready(tr.acc)

    sw, t_sw = pick("depth", run_sweep)

    # -- border: the non-core gather shape -------------------------------
    ids_border = fdbscan._compact_ids(~core_np)
    border_vals = jnp.where(core, labels0, jnp.int32(traversal.INT_MAX))

    def run_border(fn, kw):
        tr = fn(tree, segs,
                traversal.intersects(traversal.sphere(eps), ids=ids_border),
                traversal.MinLabelVisitor(border_vals, core),
                node_mask=nm_core, **kw)
        jax.block_until_ready(tr.acc)

    bd, t_bd = pick("none", run_border)

    info["timings"] = {"first_pass": t_fp, "sweep": t_sw, "border": t_bd}
    cfg = TunedConfig(first_pass=fp, sweep=sw, border=bd,
                      min_lanes=base.min_lanes, border_min_frac=0.0,
                      source="search")
    return cfg, info


def config_for(segs, tree, eps: float, min_pts: int,
               mode_name: str | None = None) -> TunedConfig:
    """The non-measured config for the active (or given) mode."""
    m = mode_name or mode()
    if m == "off":
        return PINNED
    return heuristic(segs, tree)
