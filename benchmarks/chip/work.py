"""Operations and bytes the algorithm needs, computed from shapes.

These count the work a kernel or a model step must do, not what today's
implementation touches: paged decode attention reads the K/V of the live
context of the active slots (not the whole pool the implementation
transposes), and prefill attention is causal over the real prompt tokens (not
the padded bucket).  A fix of either waste therefore raises the share of the
roofline, and no later change has to correct the count.  bf16 operands: 2 bytes
per element.
"""
from __future__ import annotations

import json
import os

BYTES = 2
PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    """The chip's peaks from ``peaks.json``; a device not in the table is an
    error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def paged_attention(a: dict, context: int) -> tuple[float, float]:
    """One decode token of one sequence attending ``context`` keys, over all
    layers: (flops, bytes).  QK^T and PV are 2·H·D flops per key each; bytes
    are the K and V of the live context plus q and o."""
    H, Hk, D, L = a["heads"], a["kv_heads"], a["head_dim"], a["layers"]
    flops = 4.0 * H * D * context
    nbytes = (2.0 * context * Hk * D + 2.0 * H * D) * BYTES
    return L * flops, L * nbytes


def flash_attention(a: dict, n: int) -> tuple[float, float]:
    """Causal self-attention over ``n`` real prompt tokens, all layers:
    (flops, bytes).  Query i sees i+1 keys: 4·H·D·n(n+1)/2 flops; bytes are
    q, k, v and o of the real tokens."""
    H, Hk, D, L = a["heads"], a["kv_heads"], a["head_dim"], a["layers"]
    flops = 2.0 * H * D * n * (n + 1)
    nbytes = (2.0 * n * H * D + 2.0 * n * Hk * D) * BYTES
    return L * flops, L * nbytes


def matmul_params_per_layer(a: dict) -> int:
    d, ff = a["d"], a["ff"]
    hq, hk = a["heads"] * a["head_dim"], a["kv_heads"] * a["head_dim"]
    return d * hq + 2 * d * hk + hq * d + 3 * d * ff


def prefill_flops(a: dict, n: int) -> float:
    """Model flops to prefill ``n`` real tokens and score the last one."""
    dense = 2.0 * matmul_params_per_layer(a) * a["layers"] * n
    return dense + flash_attention(a, n)[0] + 2.0 * a["d"] * a["vocab"]


def decode_flops(a: dict, context: int) -> float:
    """Model flops of one decode token attending ``context`` keys."""
    dense = 2.0 * matmul_params_per_layer(a) * a["layers"]
    return dense + paged_attention(a, context)[0] + 2.0 * a["d"] * a["vocab"]


def roofline_share(flops: float, nbytes: float, seconds: float, peak: dict) -> float:
    """Least time the chip could take over the time taken, in percent."""
    bound = max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * bound / seconds
