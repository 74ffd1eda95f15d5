"""Paper §II-A kernel benchmarks: 32k NTT (q=12289, Montgomery) and
SHA3-256 at the 1088-bit rate.  The kernels compile on a TPU and run
through the Pallas interpreter elsewhere; every row names the backend
its wall time came from.  Derived op counts are hardware-independent."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ntt import ops as ntt_ops, ref as ntt_ref
from repro.kernels.sha3 import ops as sha3_ops


def _time(fn, n=3):
    fn()                                   # compile/warmup
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n


def run() -> list[tuple]:
    rows = []
    rng = np.random.default_rng(0)
    dev = jax.devices()[0]
    interp = dev.platform != "tpu"
    on = f"device={dev.platform}:{dev.device_kind}" \
        + (" (interpreter)" if interp else "")

    # 32k-point NTT batch (paper benchmark shape)
    x32 = jnp.asarray(rng.integers(0, ntt_ref.Q, 32768), jnp.int32)
    dt = _time(lambda: np.asarray(ntt_ops.ntt_32k(x32, interpret=interp)))
    butterflies = 8 * (4096 // 2) * 12      # batch x N/2 x log2(N)
    rows.append(("ntt_32k_q12289", dt * 1e6,
                 f"us_per_call butterflies={butterflies} "
                 "(8x4096 batch; q caps single transform at 4096 — see EXPERIMENTS) "
                 + on))

    # negacyclic polynomial product (lattice-crypto primitive)
    a = jnp.asarray(rng.integers(0, ntt_ref.Q, 2048), jnp.int32)
    b = jnp.asarray(rng.integers(0, ntt_ref.Q, 2048), jnp.int32)
    dt = _time(lambda: np.asarray(
        ntt_ops.negacyclic_mul(a, b, interpret=interp)))
    rows.append(("negacyclic_mul_2048", dt * 1e6, "us_per_call " + on))

    # SHA3-256, 1088-bit rate: 64 x 4-block messages
    msgs = [bytes(rng.integers(0, 256, 500, dtype=np.uint8)) for _ in range(64)]
    dt = _time(lambda: sha3_ops.sha3_256(msgs, interpret=interp), n=2)
    blocks = sum(len(m) // 136 + 1 for m in msgs)
    rows.append(("sha3_256_batch64", dt * 1e6,
                 f"us_per_call keccak_blocks={blocks} rate=1088 state=1600 "
                 + on))
    return rows
