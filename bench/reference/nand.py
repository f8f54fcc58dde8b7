"""The reference's own NAND characterization: attempt-count tables and
the AR² safe tR scale, computed on the device the benchmark runs on.

The simulator samples each read's retry-attempt count from a table
characterized over a 160-chip population (threshold-voltage Gaussians,
retention and wear degradation, a charge-proportional retry table, a
hard ECC capability).  This module recomputes those tables for the
reference from the physics alone, so the reference takes no table from
the program under test.  The model and its constants are a copy of
``repro.core`` (``constants``, ``voltage``, ``retry``, ``characterize``)
as the benchmark was defined; the operations run eagerly in JAX's
default 32-bit mode, op for op as there, so on one backend the tables
agree with the program's bit for bit while both follow the same model.

Tables are kept on disk in the reference's own cache directory (set by
the harness, keyed by backend and device kind), so a checkout pays each
characterization once.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

# -- the NAND model's constants ---------------------------------------------

LEVEL_MU0 = (-1.20, 1.10, 1.70, 2.30, 2.90, 3.50, 4.10, 4.70)
LEVEL_SIGMA0 = (0.30, 0.085, 0.08, 0.08, 0.08, 0.08, 0.08, 0.085)
PAGE_BOUNDARIES = {"lsb": (1, 5), "csb": (2, 4, 6), "msb": (3, 7)}
PAGE_TYPES = ("lsb", "csb", "msb")
ALPHA_RETENTION = 0.094
SIGMA_RETENTION = 0.0020
PEC_KNEE = 2000.0
PEC_BETA = 1.1
SIGMA_WEAR = 0.014
SENSE_ETA = 0.11
RETENTION_T0_DAYS = 1.0
CHIP_VAR_SIGMA = 0.06
BLOCK_VAR_SIGMA = 0.04
PAGE_JITTER_SIGMA = 0.010
N_CHIPS = 160
RETRY_STEP_V = 0.06
MAX_RETRY_STEPS = 40
ECC_T = 72
ECC_N_BITS = 8192 + 1280
ECC_RBER_CAP = ECC_T / float(ECC_N_BITS)

#: AR² search.
TR_SCALE_GRID = (1.0, 0.95, 0.9, 0.85, 0.8, 0.75, 0.7, 0.65, 0.6)
EXTRA_ATTEMPT_BUDGET = 0.30
TR_SCALE_FLOOR = 0.7
SOTA_STEP_REDUCTION = 0.70

#: Sense times the AR² search weighs scales with (the model's own
#: timing, not a drive's).
_TR_US = {"lsb": 48.0, "csb": 61.3, "msb": 78.0}
_TDMA_US = 15.4
_TECC_US = 9.5


# -- threshold-voltage model --------------------------------------------------

def _qfunc(x):
    return 0.5 * jax.scipy.special.erfc(x / jnp.sqrt(2.0).astype(x.dtype))


def _charge_fraction():
    mu0 = jnp.asarray(LEVEL_MU0)
    return jnp.maximum(mu0, 0.0) / mu0[-1]


def _degradation_scale(retention_days, pec):
    t = jnp.asarray(retention_days, jnp.float32)
    c = jnp.asarray(pec, jnp.float32)
    return jnp.log1p(t / RETENTION_T0_DAYS) * (1.0 + c / PEC_KNEE) ** PEC_BETA


def _degraded(retention_days, pec, rate_factor):
    mu0 = jnp.asarray(LEVEL_MU0, jnp.float32)
    sigma0 = jnp.asarray(LEVEL_SIGMA0, jnp.float32)
    q = _charge_fraction()
    g = _degradation_scale(retention_days, pec) * jnp.asarray(
        rate_factor, jnp.float32)
    g = g[..., None]
    mu = mu0 - ALPHA_RETENTION * q * g
    c = jnp.asarray(pec, jnp.float32)[..., None]
    sig_ret = SIGMA_RETENTION * q * g
    sig_wear = SIGMA_WEAR * jnp.where(q > 0, 1.0, 0.0) * (c / 1000.0) ** 0.7
    sigma = jnp.sqrt(sigma0 ** 2 + sig_ret ** 2 + sig_wear ** 2)
    return mu, sigma


def _optimal_boundaries(mu, sigma):
    m1, m2 = mu[..., :-1], mu[..., 1:]
    s1, s2 = sigma[..., :-1], sigma[..., 1:]
    a = s2 ** 2 - s1 ** 2
    b = 2.0 * (s1 ** 2 * m2 - s2 ** 2 * m1)
    c = s2 ** 2 * m1 ** 2 - s1 ** 2 * m2 ** 2 - 2.0 * (s1 * s2) ** 2 * \
        jnp.log(s2 / s1)
    midpoint = 0.5 * (m1 + m2)
    disc = jnp.maximum(b ** 2 - 4.0 * a * c, 0.0)
    safe_a = jnp.where(jnp.abs(a) < 1e-9, 1.0, a)
    r1 = (-b + jnp.sqrt(disc)) / (2.0 * safe_a)
    r2 = (-b - jnp.sqrt(disc)) / (2.0 * safe_a)
    in_between1 = (r1 > m1) & (r1 < m2)
    root = jnp.where(in_between1, r1, r2)
    return jnp.where(jnp.abs(a) < 1e-9, midpoint, root)


def _retry_read_levels(step):
    mu0 = jnp.asarray(LEVEL_MU0, jnp.float32)
    sigma0 = jnp.asarray(LEVEL_SIGMA0, jnp.float32)
    base_levels = _optimal_boundaries(mu0, sigma0)
    q = _charge_fraction()
    qb = 0.5 * (q[:-1] + q[1:])
    k = jnp.asarray(step, jnp.float32)[..., None]
    return base_levels - k * RETRY_STEP_V * qb


def _page_mask(page_type):
    bounds = PAGE_BOUNDARIES[page_type]
    return jnp.asarray(
        tuple(1.0 if (b + 1) in bounds else 0.0 for b in range(7)),
        jnp.float32)


def _rber(mu, sigma, read_levels, page_type, tr_scale):
    s = jnp.asarray(tr_scale, jnp.float32)
    extra = SENSE_ETA * jnp.maximum(1.0 - s, 0.0)
    sig = jnp.sqrt(sigma ** 2 + extra[..., None] ** 2)
    m_lo, m_hi = mu[..., :-1], mu[..., 1:]
    s_lo, s_hi = sig[..., :-1], sig[..., 1:]
    up = _qfunc((read_levels - m_lo) / s_lo)
    dn = _qfunc((m_hi - read_levels) / s_hi)
    per_boundary = (up + dn) / 8.0
    return jnp.sum(per_boundary * _page_mask(page_type), axis=-1)


def _rber_per_retry_step(mu, sigma, page_type, tr_scale, level_jitter):
    steps = jnp.arange(MAX_RETRY_STEPS + 1, dtype=jnp.float32)
    levels = _retry_read_levels(steps)
    levels = levels + level_jitter[..., None, :]
    return _rber(mu[..., None, :], sigma[..., None, :], levels, page_type,
                 tr_scale)


def _process_variation(key, n_chips, n_blocks):
    k1, k2 = jax.random.split(key)
    chip = jnp.exp(CHIP_VAR_SIGMA * jax.random.normal(k1, (n_chips, 1)))
    block = jnp.exp(BLOCK_VAR_SIGMA * jax.random.normal(k2,
                                                        (n_chips, n_blocks)))
    return chip * block


def _first_success(rber_steps, start_step=0):
    steps = jnp.arange(rber_steps.shape[-1])
    ok = (rber_steps <= ECC_RBER_CAP) & \
        (steps >= jnp.asarray(start_step)[..., None])
    any_ok = jnp.any(ok, axis=-1)
    idx = jnp.argmax(ok, axis=-1)
    return jnp.where(any_ok, idx, MAX_RETRY_STEPS)


def _population(key, retention_days, pec, page_type, n_pages, tr_scale):
    """(chips, blocks, pages, steps) RBER of one page type, and the key
    left over for the SOTA predictor's noise."""
    k_var, k_jit = jax.random.split(key)
    rate = _process_variation(k_var, N_CHIPS, 8)
    mu, sigma = _degraded(jnp.float32(retention_days), jnp.float32(pec),
                          rate)
    jitter = PAGE_JITTER_SIGMA * jax.random.normal(
        k_jit, (N_CHIPS, 8, n_pages, 7))
    return _rber_per_retry_step(mu[..., None, :], sigma[..., None, :],
                                page_type, tr_scale, jitter)


# -- on-disk table cache ------------------------------------------------------

def _cache_path(kind: str, **kw) -> Optional[str]:
    d = os.environ.get("BENCH_REFERENCE_CACHE_DIR")
    if not d:
        return None
    dev = jax.devices()[0]
    kw.update(backend=dev.platform, device_kind=dev.device_kind)
    blob = repr((kind, sorted(kw.items())))
    return os.path.join(d, f"{kind}_{hashlib.sha1(blob.encode()).hexdigest()[:24]}.json")


def _cached(kind: str, compute, **kw):
    path = _cache_path(kind, **kw)
    if path is not None and os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    value = compute()
    if path is not None:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(value, f)
        os.replace(tmp, path)
    return value


# -- the two tables the simulator reads ---------------------------------------

def _pipelined_latency(n_attempts, page_type, tr_scale):
    n = np.asarray(n_attempts, np.float64)
    tr = _TR_US[page_type] * tr_scale
    td = _TDMA_US + _TECC_US
    return tr + np.maximum(n - 1, 0) * max(tr, td) + td


def _safe_scale_compute(retention_days: float, pec: float) -> float:
    safe = []
    for i, pt in enumerate(PAGE_TYPES):
        key = jax.random.fold_in(jax.random.PRNGKey(0), i)
        rber = _population(key, retention_days, pec, pt, 16, 1.0)
        k = _first_success(rber)
        mean_1 = float(jnp.mean(k + 1))
        best_s, best_lat = 1.0, None
        for s in TR_SCALE_GRID:
            if s < TR_SCALE_FLOOR:
                break
            k_s = _first_success(
                _population(key, retention_days, pec, pt, 16, float(s)))
            if float(jnp.mean(k_s + 1)) > mean_1 + EXTRA_ATTEMPT_BUDGET:
                continue
            lat = float(np.mean(_pipelined_latency(np.asarray(k_s + 1), pt,
                                                   float(s))))
            if best_lat is None or lat < best_lat:
                best_s, best_lat = float(s), lat
        safe.append(best_s)
    return float(max(safe))


@functools.lru_cache(maxsize=None)
def safe_tr_scale(retention_days: float, pec: float) -> float:
    """AR²'s safe sense-time scale at one operating condition: the scale
    of least mean pipelined read latency among those that add at most
    ``EXTRA_ATTEMPT_BUDGET`` attempts, taken safe for all page types."""
    return _cached("scale",
                   lambda: _safe_scale_compute(retention_days, pec),
                   retention_days=retention_days, pec=pec)


def _cdf_compute(retention_days, pec, page_type, sota, tr_scale):
    key = jax.random.fold_in(jax.random.PRNGKey(101),
                             PAGE_TYPES.index(page_type))
    k_var, k_jit, k_sota = jax.random.split(key, 3)
    rate = _process_variation(k_var, N_CHIPS, 8)
    mu, sigma = _degraded(jnp.float32(retention_days), jnp.float32(pec),
                          rate)
    jitter = PAGE_JITTER_SIGMA * jax.random.normal(k_jit, (N_CHIPS, 8, 32, 7))
    rber = _rber_per_retry_step(mu[..., None, :], sigma[..., None, :],
                                page_type, tr_scale, jitter)
    k_default = _first_success(rber)
    if sota:
        pred = jnp.floor(SOTA_STEP_REDUCTION * k_default.astype(jnp.float32))
        pred = pred + jax.random.randint(k_sota, k_default.shape, -1, 1)
        start = jnp.clip(pred, 0, None).astype(jnp.int32)
    else:
        start = jnp.zeros_like(k_default)
    k = _first_success(rber, start)
    attempts = np.asarray((k - start + 1).astype(jnp.int32)).ravel()
    max_attempts = MAX_RETRY_STEPS + 1
    counts = np.bincount(np.clip(attempts, 0, max_attempts),
                         minlength=max_attempts + 1).astype(np.float64)
    return np.cumsum(counts / counts.sum()).tolist()


@functools.lru_cache(maxsize=None)
def attempt_cdf(retention_days: float, pec: float, page_type: str,
                sota: bool, tr_scale: float) -> np.ndarray:
    """Cumulative distribution of read attempts (index = attempts) for
    one page type under one mechanism, over the chip population."""
    cdf = _cached("cdf",
                  lambda: _cdf_compute(retention_days, pec, page_type, sota,
                                       tr_scale),
                  retention_days=retention_days, pec=pec,
                  page_type=page_type, sota=sota, tr_scale=tr_scale)
    return np.asarray(cdf, np.float64)
