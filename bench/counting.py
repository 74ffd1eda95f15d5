"""Operations and bytes the algorithm needs, from shapes and lengths.

These count what serving a request requires, not what the compiled
program happens to do: padding, recomputation and reads of dead lanes
are not counted, so a change that removes such work does not move the
denominator.  ``m`` is a configuration's ``model`` block.

A request with a prompt of ``P`` tokens and ``n`` tokens served costs
one prefill of ``P`` tokens (the head once, for the first token) and
``n - 1`` decode steps; decode step ``j`` (1-based) reads the key and
value rows of ``P + j`` positions.
"""
from __future__ import annotations

BF16 = 2


def layer_params(m: dict) -> int:
    D, H, K, hd, F = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                      m["head_dim"], m["d_ff"])
    attn = D * H * hd + 2 * D * K * hd + H * hd * D
    mlp = D * F * (3 if m["gated_mlp"] else 2)
    return attn + mlp


def _attn_flops_per_slot(m: dict) -> int:
    """QK^T and PV for one query against one key row, all layers."""
    return 4 * m["num_heads"] * m["head_dim"] * m["num_layers"]


def decode_slots(P: int, n: int) -> int:
    """Key/value positions read over a request's ``n - 1`` decode steps."""
    k = max(n - 1, 0)
    return k * P + k * (k + 1) // 2


def request_flops(m: dict, P: int, n: int) -> int:
    """Model FLOPs of serving one request (prefill + decode)."""
    lin = 2 * m["num_layers"] * layer_params(m)
    head = 2 * m["d_model"] * m["vocab_size"]
    tokens = P + max(n - 1, 0)
    prefill_slots = P * (P + 1) // 2
    return (lin * tokens + head * (1 + max(n - 1, 0))
            + _attn_flops_per_slot(m) * (prefill_slots + decode_slots(P, n)))


def kv_row_bytes(m: dict) -> int:
    """Key plus value bytes of one position, all layers."""
    return 2 * m["num_kv_heads"] * m["head_dim"] * BF16 * m["num_layers"]


def decode_kv_bytes(m: dict, P: int, n: int) -> int:
    """Bytes the paged attention must read over a request's decode."""
    return kv_row_bytes(m) * decode_slots(P, n)
