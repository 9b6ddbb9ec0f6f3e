"""Operations and bytes the work needs, computed from shapes.

These are the algorithm's needs, not what the implementation moves: a
decode step needs its weights, the K/V of each row's real length and the
K/V it writes; a swap-kernel call needs the rows it reads and writes.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Sequence

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict:
    """The peak table's row for ``device_kind``; a device not in the
    table is an error."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def _dims(arch):
    return (arch.vocab, arch.d_model, arch.n_layers, arch.n_heads,
            arch.n_kv_heads, arch.head_dim_, arch.d_ff)


def layer_matmul_params(arch) -> int:
    """Weights of one layer's matrix products (q, k, v, o, gate, up, down)."""
    _, D, _, H, KV, hd, F = _dims(arch)
    return D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F


def param_count(arch) -> int:
    V, D, L, H, KV, hd, _ = _dims(arch)
    per_layer = layer_matmul_params(arch) + 2 * D
    if arch.qkv_bias:
        per_layer += (H + 2 * KV) * hd
    if arch.qk_norm:
        per_layer += 2 * hd
    return V * D * (1 if arch.tie_embeddings else 2) + L * per_layer + D


def kv_token_bytes(arch, dtype_bytes: int = 2) -> int:
    """K and V of one token over all layers."""
    return arch.n_layers * 2 * arch.n_kv_heads * arch.head_dim_ * dtype_bytes


def decode_flops(arch, kv_lens: Sequence[int]) -> float:
    """Model FLOPs of one decode step over rows whose caches hold
    ``kv_lens`` tokens before the step: the products with every weight
    matrix and the output head, and attention over kv_len + 1 positions."""
    V, D, L, H, _, hd, _ = _dims(arch)
    rows = len(kv_lens)
    dense = 2.0 * (L * layer_matmul_params(arch) + D * V) * rows
    attn = 4.0 * L * H * hd * float(sum(int(n) + 1 for n in kv_lens))
    return dense + attn


def decode_bytes(arch, kv_lens: Sequence[int], dtype_bytes: int = 2) -> float:
    """HBM bytes one decode step needs: every weight once (the embedding
    table only for the rows' tokens, unless it is also the output head),
    the K/V of each row's ``kv_len`` tokens, and the K/V it writes."""
    V, D, _, _, _, _, _ = _dims(arch)
    rows = len(kv_lens)
    weights = param_count(arch) - V * D + rows * D
    if arch.tie_embeddings:
        weights += V * D                      # the head reads the whole table
    kv = kv_token_bytes(arch, dtype_bytes) * (float(sum(int(n) for n in kv_lens)) + rows)
    return weights * dtype_bytes + kv


# swap kernels: blocks in the word layout (n, R, 128) uint32 = n rows of
# R * 512 bytes
ROW_WORD_BYTES = 128 * 4


def swap_kernel_bytes(kernel: str, n: int, rows_r: int) -> float:
    """HBM bytes one call of a swap kernel needs for ``n`` MPs of
    ``rows_r`` 512-byte rows each."""
    mp = n * rows_r * ROW_WORD_BYTES
    if kernel in ("gather_blocks", "scatter_blocks"):
        return 2.0 * mp                       # read n MPs, write n MPs
    if kernel in ("zero_detect", "fletcher_checksum"):
        return mp + n * ROW_WORD_BYTES        # read n MPs, one row out each
    raise KeyError(kernel)


SWAP_KERNELS = ("gather_blocks", "scatter_blocks", "zero_detect",
                "fletcher_checksum")
