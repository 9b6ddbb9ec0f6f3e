"""Operations and bytes the work needs, computed from shapes.

These are the algorithm's needs, not what the implementation moves: a
decode step needs its weights, the K/V of each row's real length and the
K/V it writes (its model family's module, ``bench/families/``, counts
them); a swap-kernel call needs the rows it reads and writes.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Optional, Sequence

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


def _family(arch, root):
    from bench.cell import family_module

    return family_module(arch.family, root)


def layer_matmul_params(arch, root: Optional[Path] = None) -> int:
    """Weights of one layer's matrix products."""
    return _family(arch, root).layer_matmul_params(arch)


def param_count(arch, root: Optional[Path] = None) -> int:
    return _family(arch, root).param_count(arch)


def kv_token_bytes(arch, dtype_bytes: int = 2, root: Optional[Path] = None) -> int:
    """K and V of one token over all layers."""
    return _family(arch, root).kv_token_bytes(arch, dtype_bytes)


def decode_flops(arch, kv_lens: Sequence[int], root: Optional[Path] = None) -> float:
    """Model FLOPs of one decode step over rows whose caches hold
    ``kv_lens`` tokens before the step."""
    return _family(arch, root).decode_flops(arch, kv_lens)


def decode_bytes(arch, kv_lens: Sequence[int], dtype_bytes: int = 2,
                 root: Optional[Path] = None) -> float:
    """HBM bytes one decode step needs over rows whose caches hold
    ``kv_lens`` tokens before the step."""
    return _family(arch, root).decode_bytes(arch, kv_lens, dtype_bytes)


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
