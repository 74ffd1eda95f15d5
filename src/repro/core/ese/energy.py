"""ESE data-center energy model (paper §II-C, Fig 4(a)).

Operational energy: per-step chip power from roofline-term utilizations
(compute/HBM/ICI), plus idle equipment, host share, power-delivery loss
and cooling (PUE) — the components the paper enumerates.  A learned MLP
head (the paper trains a CNN on measured partitions; we train on a
synthetic measurement generator) refines the white-box estimate.

Embodied energy: the paper's linear model
    E_emb = Σ_{i∈X} TBE_i · latency_i / lifetime_i        (embodied.py)
"""
from __future__ import annotations

from collections.abc import Mapping
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import hw
from repro.core.ese.records import RooflineRecord

# fraction of dynamic power attributed to each subsystem at full tilt
W_COMPUTE, W_MEMORY, W_ICI = 0.55, 0.33, 0.12
DELIVERY_LOSS = 0.06            # power delivery overhead


@dataclass(frozen=True)
class StepEnergy:
    chip_w: float               # mean per-chip power during the step
    step_j: float               # whole-job energy for one step (all chips)
    breakdown: dict

    def per_token_j(self, tokens: int) -> float:
        return self.step_j / max(tokens, 1)


def operational_step_energy(roofline: RooflineRecord,
                            chips: int | None = None) -> StepEnergy:
    """White-box model from a typed dry-run record (§Roofline terms).

    ``chips`` defaults to ``roofline.chips``; raw dicts are rejected —
    go through ``RooflineRecord.from_dict`` (or the legacy
    ``estimator.estimate_task`` adapter) first.
    """
    if isinstance(roofline, Mapping):
        raise TypeError(
            "operational_step_energy now takes a RooflineRecord; build one "
            "with RooflineRecord.from_dict(...) or call the legacy "
            "estimator.estimate_task dict adapter")
    chips = roofline.chips if chips is None else int(chips)
    t = max(roofline.step_time_bound_s, 1e-9)
    u_c = roofline.t_compute_s / t
    u_m = roofline.t_memory_s / t
    u_i = roofline.t_collective_s / t
    dyn = (hw.V5E.tdp_w - hw.V5E.idle_w)
    chip_w = hw.V5E.idle_w + dyn * (W_COMPUTE * u_c + W_MEMORY * u_m + W_ICI * u_i)
    total_w = (chip_w + hw.HOST_OVERHEAD_W) * chips
    total_w *= (1.0 + DELIVERY_LOSS) * hw.PUE
    return StepEnergy(
        chip_w=chip_w,
        step_j=total_w * t,
        breakdown={
            "compute_util": u_c, "memory_util": u_m, "ici_util": u_i,
            "chip_w": chip_w, "facility_w": total_w, "step_s": t,
        },
    )


# ---------------------------------------------------------------------------
# Learned refinement head (paper: CNN on static+runtime features; here an
# MLP on dry-run features, trained against a synthetic measurement
# generator with hidden inefficiencies)
# ---------------------------------------------------------------------------

FEATURES = (
    "t_compute_s", "t_memory_s", "t_collective_s",
    "flops_per_device", "hbm_bytes_per_device", "collective_bytes_per_device",
)


def _featurize(recs: list[RooflineRecord]) -> np.ndarray:
    rows = []
    for rl in recs:
        rows.append([np.log1p(float(getattr(rl, k))) for k in FEATURES])
    return np.asarray(rows, np.float32)


def synthetic_measurement(rl: RooflineRecord, rng) -> float:
    """Hidden 'real hardware' generator: imperfect overlap + fixed launch
    overhead + noise.  Stands in for the paper's profiler measurements."""
    t = (max(rl.t_compute_s, rl.t_memory_s, rl.t_collective_s)
         + 0.25 * (rl.t_compute_s + rl.t_memory_s + rl.t_collective_s)
         + 2e-3)
    return t * float(rng.lognormal(0.0, 0.05))


def init_mlp(key, nin, hidden=32):
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (nin, hidden)) * (1 / np.sqrt(nin)),
        "b1": jnp.zeros((hidden,)),
        "w2": jax.random.normal(k2, (hidden, 1)) * (1 / np.sqrt(hidden)),
        "b2": jnp.zeros((1,)),
    }


def mlp_forward(p, x):
    h = jnp.tanh(x @ p["w1"] + p["b1"])
    return (h @ p["w2"] + p["b2"])[..., 0]


class LatencyHead(NamedTuple):
    """Learned latency refinement — unpacks like the legacy
    (params, norm, mape) tuple."""
    params: dict
    norm: dict
    mape: float


def train_latency_head(records: list[RooflineRecord], seed: int = 0,
                       steps: int = 600) -> LatencyHead:
    """Fit log-latency from dry-run features against the synthetic
    measurement generator.  ``records`` are typed ``RooflineRecord``s
    (use ``records.roofline_records(cells)`` on raw dry-run JSON)."""
    rng = np.random.default_rng(seed)
    recs = [r for r in records if isinstance(r, RooflineRecord)]
    if len(recs) != len(records):
        raise TypeError(
            "train_latency_head takes RooflineRecords; convert dry-run "
            "cells with records.roofline_records(...) first")
    x = _featurize(recs)
    y = np.asarray(
        [np.log(synthetic_measurement(r, rng)) for r in recs],
        np.float32,
    )
    mu, sd = x.mean(0), x.std(0) + 1e-9
    xn = (x - mu) / sd
    n_tr = max(2, int(0.8 * len(xn)))
    params = init_mlp(jax.random.PRNGKey(seed), xn.shape[1])
    xt, yt = jnp.asarray(xn[:n_tr]), jnp.asarray(y[:n_tr])

    @jax.jit
    def step(p, opt):
        loss, g = jax.value_and_grad(
            lambda pp: jnp.mean((mlp_forward(pp, xt) - yt) ** 2)
        )(p)
        opt = jax.tree.map(lambda m, gg: 0.9 * m + 0.1 * gg, opt, g)
        p = jax.tree.map(lambda w, m: w - 3e-2 * m / (jnp.abs(m) + 1e-3), p, opt)
        return p, opt, loss

    opt = jax.tree.map(jnp.zeros_like, params)
    for _ in range(steps):
        params, opt, loss = step(params, opt)

    pred = np.exp(np.asarray(mlp_forward(params, jnp.asarray(xn[n_tr:]))))
    true = np.exp(y[n_tr:])
    mape = float(np.mean(np.abs(pred - true) / true)) if len(true) else 0.0
    return LatencyHead(params, {"mu": mu, "sd": sd}, mape)


def predict_latency(params, norm, record: RooflineRecord) -> float:
    x = (_featurize([record]) - norm["mu"]) / norm["sd"]
    return float(np.exp(np.asarray(mlp_forward(params, jnp.asarray(x)))[0]))
