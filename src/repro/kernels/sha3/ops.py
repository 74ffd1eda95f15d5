"""jit'd SHA3-256 over the Pallas Keccak kernel + checkpoint hashing.

``sha3_256`` is the TPU-path batch hasher (rate 1088 / state 1600 per
the paper's benchmark).  It compiles the kernel unless the caller
passes ``interpret=True`` (the Pallas interpreter: CPU tests/hosts).
The checkpoint manager hashes shards with this code path's semantics;
on CPU hosts it may use hashlib (identical digests — property-tested)
for speed.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.kernels.sha3 import ref
from repro.kernels.sha3.sha3 import keccak_f_pallas


def _to_pairs(state64: np.ndarray) -> np.ndarray:
    return np.stack([
        (state64 & np.uint64(0xFFFFFFFF)).astype(np.uint32),
        (state64 >> np.uint64(32)).astype(np.uint32),
    ], axis=-1)


def _to_u64(pairs: np.ndarray) -> np.ndarray:
    return (pairs[..., 1].astype(np.uint64) << np.uint64(32)) \
        | pairs[..., 0].astype(np.uint64)


def sha3_256(msgs: list[bytes], interpret: bool = False) -> list[bytes]:
    """Batched SHA3-256 via the Pallas Keccak-f kernel."""
    blocks, nb = ref.pad_messages(msgs)          # (B, max_blocks, 17) u64
    B, max_blocks, _ = blocks.shape
    state = np.zeros((B, 25), np.uint64)
    for blk in range(max_blocks):
        active = blk < nb
        xored = state.copy()
        xored[:, :17] ^= blocks[:, blk]
        pairs = jnp.asarray(_to_pairs(xored))
        out = _to_u64(np.asarray(keccak_f_pallas(pairs, interpret=interpret)))
        state = np.where(active[:, None], out, state)
    dig = state[:, :4].copy().view(np.uint8).reshape(B, 32)
    return [bytes(dig[i]) for i in range(B)]


def hash_bytes(data: bytes, interpret: bool = False) -> bytes:
    return sha3_256([data], interpret=interpret)[0]


def hash_array(x, interpret: bool = False) -> bytes:
    """Digest of a tensor's raw bytes (checkpoint shard integrity)."""
    return hash_bytes(np.ascontiguousarray(np.asarray(x)).tobytes(),
                      interpret=interpret)
