"""160-chip characterization harness — the paper's §3 observations.

Reproduces the paper's three characterization results over a population of
simulated chips with process variation (the paper used 160 real 3D TLC
chips; our population is 160 calibrated analytical chips):

  Observation 1: reads frequently need multiple retry steps even at modest
    conditions (mean ~= 4.5 retry steps @ 3-month retention, 0 P/E).
  Observation 2: when read-retry succeeds, the final step has a large
    ECC-capability margin, even at the worst prescribed condition
    (1-year retention, 1.5K P/E cycles).
  Observation 3: the margin buys a safe tR reduction of 25% worst-case —
    the AR² table maps operating condition -> best (smallest safe) tR scale
    without ever increasing the attempt count.

The safe-scale table produced here *is* AR²'s lookup table; the simulator
and the serving/data-path integrations consume it through
:func:`lookup_tr_scale`.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import os
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import constants as C
from repro.core import ecc as ecc_mod
from repro.core import retry as R
from repro.core import voltage as V
from repro.core.constants import NandParams, DEFAULT_NAND

#: Operating-condition grid used throughout (days, P/E cycles).
RETENTION_GRID_DAYS = (0.0, 7.0, 30.0, 90.0, 180.0, 365.0)
PEC_GRID = (0.0, 500.0, 1000.0, 1500.0)

#: Candidate tR scales for the AR² search (1.0 = full sensing time).
TR_SCALE_GRID = (1.0, 0.95, 0.9, 0.85, 0.8, 0.75, 0.7, 0.65, 0.6)

#: AR² acceptance: the expected attempt count with reduced tR may exceed
#: the full-tR expectation by at most this many attempts (the paper's
#: "without increasing the number of retry steps", enforced statistically
#: per operating condition — an aggressive scale makes tail pages
#: undecodable at every table entry, which blows this budget and rejects
#: the scale).
EXTRA_ATTEMPT_BUDGET = 0.30

#: Never sense faster than this regardless of margin (circuit floor).
TR_SCALE_FLOOR = 0.7


# -- on-disk characterization cache ----------------------------------------
#
# The JAX population characterization costs seconds per (condition, scale)
# cell and is pure in its arguments, so results are also persisted across
# processes.  Benchmark sweeps (simulate_batch, e2e, microbench) then pay
# each characterization once per machine, not once per run.  Disable with
# REPRO_CHAR_CACHE=0; relocate with REPRO_CHAR_CACHE_DIR.

_CHAR_CACHE_VERSION = 1


def _char_cache_dir() -> Optional[str]:
    if os.environ.get("REPRO_CHAR_CACHE", "1") == "0":
        return None
    return os.environ.get("REPRO_CHAR_CACHE_DIR") or os.path.join(
        os.path.expanduser("~"), ".cache", "repro_flashsim"
    )


def _char_cache_path(kind: str, ext: str, **kw) -> Optional[str]:
    d = _char_cache_dir()
    if d is None:
        return None
    # The backend is part of the key: tables characterized on one
    # platform never serve a run on another.
    kw["backend"] = jax.default_backend()
    blob = repr((_CHAR_CACHE_VERSION, kind, sorted(kw.items())))
    h = hashlib.sha1(blob.encode()).hexdigest()[:24]
    return os.path.join(d, f"{kind}_{h}.{ext}")


def _char_cache_load(path: Optional[str]):
    if path is None or not os.path.exists(path):
        return None
    try:
        if path.endswith(".npy"):
            return np.load(path)
        with open(path) as f:
            return json.load(f)
    except Exception:
        return None  # corrupt/partial entry: fall through to recompute


def _char_cache_store(path: Optional[str], value) -> None:
    if path is None:
        return
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp"
        if path.endswith(".npy"):
            with open(tmp, "wb") as f:
                np.save(f, value)
        else:
            with open(tmp, "w") as f:
                json.dump(value, f)
        os.replace(tmp, path)
    except Exception:
        pass  # cache is best-effort; never fail the computation


@dataclasses.dataclass(frozen=True)
class ConditionStats:
    retention_days: float
    pec: float
    mean_retry_steps: float        # attempts - 1, averaged over population
    p99_retry_steps: float
    frac_reads_with_retry: float   # P[attempts > 1]
    mean_margin_final: float       # ECC-capability margin at success entry
    p01_margin_final: float        # 1st-percentile margin (worst pages)
    safe_tr_scale: float           # AR² table entry


def _population_rber(
    key: jax.Array,
    retention_days: float,
    pec: float,
    page_type: str,
    n_chips: int,
    n_blocks: int,
    n_pages: int,
    tr_scale,
    params: NandParams,
) -> jax.Array:
    """(chips, blocks, pages, steps) RBER tensor for one page type."""
    k_var, k_jit = jax.random.split(key)
    rate = V.sample_process_variation(k_var, n_chips, n_blocks, params)
    mu, sigma = V.degraded_distributions(
        jnp.float32(retention_days), jnp.float32(pec), rate, params
    )
    jitter = C.PAGE_JITTER_SIGMA * jax.random.normal(
        k_jit, (n_chips, n_blocks, n_pages, 7)
    )
    return R.rber_per_retry_step(
        mu[..., None, :], sigma[..., None, :], page_type,
        tr_scale, level_jitter=jitter, params=params,
    )


@functools.lru_cache(maxsize=256)
def characterize_condition(
    retention_days: float,
    pec: float,
    n_chips: int = C.N_CHIPS,
    n_blocks: int = 8,
    n_pages: int = 16,
    seed: int = 0,
    params: NandParams = DEFAULT_NAND,
) -> ConditionStats:
    """Full characterization of one operating condition (cached)."""
    cache_path = _char_cache_path(
        "cond", "json",
        retention_days=retention_days, pec=pec, n_chips=n_chips,
        n_blocks=n_blocks, n_pages=n_pages, seed=seed, params=repr(params),
        ecc=repr(ecc_mod.DEFAULT_ECC),
    )
    cached = _char_cache_load(cache_path)
    if cached is not None:
        try:
            return ConditionStats(**cached)
        except TypeError:
            pass  # entry from an older ConditionStats schema: recompute
    cap = ecc_mod.DEFAULT_ECC.rber_cap
    steps_all, margins_all = [], []
    safe_scales = []
    for i, pt in enumerate(C.PAGE_TYPES):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
        rber = _population_rber(
            key, retention_days, pec, pt, n_chips, n_blocks, n_pages, 1.0, params
        )
        k = R.first_success_step(rber)                       # (C, B, P)
        rber_final = jnp.take_along_axis(rber, k[..., None], axis=-1)[..., 0]
        margin = ecc_mod.capability_margin(rber_final)
        steps_all.append(np.asarray(k))
        margins_all.append(np.asarray(margin))

        # AR² search: re-run the *whole* retry search at each candidate
        # scale (every attempt senses faster, per the paper).  A scale is
        # admissible if the expected attempt count stays within
        # ATTEMPT_RATIO_BUDGET of full-tR; among admissible scales pick the
        # one minimizing expected pipelined read latency (the paper's
        # "best tR value for a certain operating condition").
        from repro.core import timing as T

        mean_attempts_1 = float(jnp.mean(k + 1))
        best_s, best_lat = 1.0, None
        for s in TR_SCALE_GRID:
            if s < TR_SCALE_FLOOR:
                break
            rber_s = _population_rber(
                key, retention_days, pec, pt, n_chips, n_blocks, n_pages,
                float(s), params,
            )
            k_s = R.first_success_step(rber_s, max_steps=params.max_retry_steps)
            mean_attempts_s = float(jnp.mean(k_s + 1))
            if mean_attempts_s > mean_attempts_1 + EXTRA_ATTEMPT_BUDGET:
                continue
            lat = float(
                np.mean(
                    T.pipelined_read_latency(
                        np.asarray(k_s + 1), page_type=pt, tr_scale=float(s)
                    )
                )
            )
            if best_lat is None or lat < best_lat:
                best_s, best_lat = float(s), lat
        safe_scales.append(best_s)

    steps = np.concatenate([s.ravel() for s in steps_all])
    margins = np.concatenate([m.ravel() for m in margins_all])
    stats = ConditionStats(
        retention_days=retention_days,
        pec=pec,
        mean_retry_steps=float(steps.mean()),
        p99_retry_steps=float(np.percentile(steps, 99)),
        frac_reads_with_retry=float((steps > 0).mean()),
        mean_margin_final=float(margins.mean()),
        p01_margin_final=float(np.percentile(margins, 1)),
        safe_tr_scale=float(max(safe_scales)),  # safe for ALL page types
    )
    _char_cache_store(cache_path, dataclasses.asdict(stats))
    return stats


@functools.lru_cache(maxsize=8)
def safe_tr_table(
    retentions: Tuple[float, ...] = RETENTION_GRID_DAYS,
    pecs: Tuple[float, ...] = PEC_GRID,
    seed: int = 0,
) -> Dict[Tuple[float, float], float]:
    """AR²'s condition -> best-safe-tR-scale lookup table."""
    return {
        (r, p): characterize_condition(r, p, seed=seed).safe_tr_scale
        for r in retentions
        for p in pecs
    }


def snap_pec(pec: float) -> float:
    """Snap a continuous P/E count *up* to the characterization grid.

    Used for per-block condition resolution: a block worn past its bin is
    characterized at the next-worse bin (data only gets older, wear only
    grows), keeping the set of distinct characterizations bounded by
    ``PEC_GRID`` regardless of how many wear levels a trace produces.
    """
    for p in PEC_GRID:
        if p >= pec:
            return float(p)
    return float(PEC_GRID[-1])


def lookup_tr_scale(retention_days: float, pec: float) -> float:
    """AR² table lookup with conservative (next-worse-bin) snapping.

    Characterizes only the snapped bin (cached) — building the full grid
    eagerly costs minutes on CPU and is only needed by the table benchmark.
    """
    # Snap *up* to the next characterized bin when between bins (data only
    # gets older), and likewise for wear — conservative by construction.
    r_candidates = [r for r in RETENTION_GRID_DAYS if r >= retention_days]
    r_bin = r_candidates[0] if r_candidates else RETENTION_GRID_DAYS[-1]
    return characterize_condition(r_bin, snap_pec(pec)).safe_tr_scale


@functools.lru_cache(maxsize=512)
def attempt_histogram(
    retention_days: float,
    pec: float,
    page_type: str = "csb",
    sota: bool = False,
    tr_scale: float = 1.0,
    seed: int = 0,
    max_attempts: int = C.MAX_RETRY_STEPS + 1,
) -> np.ndarray:
    """Empirical attempt-count distribution for one page type (cached).

    The SSD simulator samples per-read attempt counts from this histogram
    (normalized).  ``tr_scale`` < 1 models AR²: the whole retry search runs
    at reduced sensing time, so the occasional extra attempt it induces is
    captured faithfully.  Shape: (max_attempts + 1,); index = attempts.
    """
    cache_path = _char_cache_path(
        "hist", "npy",
        retention_days=retention_days, pec=pec, page_type=page_type,
        sota=sota, tr_scale=tr_scale, seed=seed, max_attempts=max_attempts,
        # The histogram depends on the NAND/ECC model this build uses;
        # key them in so model changes invalidate stale on-disk entries.
        params=repr(DEFAULT_NAND), ecc_cap=C.ECC_RBER_CAP,
    )
    cached = _char_cache_load(cache_path)
    if cached is not None and cached.shape == (max_attempts + 1,):
        return cached
    key = jax.random.fold_in(
        jax.random.PRNGKey(seed + 101), C.PAGE_TYPES.index(page_type)
    )
    attempts, _ = R.attempts_for_population(
        key, retention_days, pec, page_type, sota=sota, tr_scale=tr_scale
    )
    a = np.asarray(attempts).ravel()
    counts = np.bincount(
        np.clip(a, 0, max_attempts), minlength=max_attempts + 1
    ).astype(np.float64)
    hist = counts / counts.sum()
    _char_cache_store(cache_path, hist)
    return hist


@functools.lru_cache(maxsize=512)
def attempt_cdf(
    retention_days: float,
    pec: float,
    page_type: str = "csb",
    sota: bool = False,
    tr_scale: float = 1.0,
    seed: int = 0,
    max_attempts: int = C.MAX_RETRY_STEPS + 1,
) -> np.ndarray:
    """Cumulative form of :func:`attempt_histogram` (cached, read-only).

    The SSD simulator inverse-CDF-samples per-read attempt counts from
    this; caching the cumsum here lets every SSDSim instance of a sweep
    share one table instead of re-accumulating the histogram.
    """
    cdf = np.cumsum(
        attempt_histogram(
            retention_days, pec, page_type=page_type, sota=sota,
            tr_scale=tr_scale, seed=seed, max_attempts=max_attempts,
        )
    )
    cdf.setflags(write=False)
    return cdf
