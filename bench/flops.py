"""Operations and bytes from shapes: each optimizer kernel call's nominal
operations and least bytes, the chip's peaks, and the counts a traced run
takes from a cell's shapes alone.

Nothing here reads the program.  The model's training FLOPs per token are
each configuration's own: ``flops_per_token(model, seq)`` in its plain
reference ``bench/configs/<config>.py``, so that a configuration of any
family joins as new files.  A kernel's least bytes read each input once and
write each output once.
"""
from __future__ import annotations

import json
import os

import jax

from bench import reflib

F32 = 4


def peaks(device_kind: str) -> dict:
    """The chip's peaks from ``bench/peaks.json``; an unknown kind is an
    error, never a default."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def pool_groups(param_shapes: list, block_size: int) -> dict:
    """``{(bm, bn): N}``: how many blocks of each shape the model's matrix
    leaves make (zero-padded tiles of at most ``block_size``)."""
    groups = {}
    for s in param_shapes:
        lay = reflib.block_layout(tuple(s), block_size)
        if lay is None:
            continue
        S, _, _, bm, bn, mb, nb = lay
        groups[(bm, bn)] = groups.get((bm, bn), 0) + S * mb * nb
    return groups


def shape_counts(cell) -> dict:
    """What a traced run of ``cell`` computes from shapes alone:
    ``flops_per_token`` (the configuration's reference counts it),
    ``pool_groups`` and ``rank`` of the mix."""
    h, c = cell.mix["hyper"], cell.config
    shapes = jax.tree.leaves(cell.model_ref.param_shapes(c["model"]),
                             is_leaf=lambda x: isinstance(x, tuple))
    return {"flops_per_token": float(cell.model_ref.flops_per_token(
                c["model"], c["seq"])),
            "pool_groups": pool_groups(shapes, h.get("block_size", 1)),
            "rank": h.get("rank", 0)}


def gram_calls(groups: dict, rank: int) -> list:
    """One refresh's Gram calls ``C = M^T M`` with ``M`` of ``(N, d, k)``:
    left ``d = bm, k = min(rank, bm) + bn``, right the transpose."""
    out = []
    for (bm, bn), N in sorted(groups.items()):
        out.append((N, bm, min(rank, bm) + bn))
        out.append((N, bn, min(rank, bn) + bm))
    return out


def gram_cost(N: int, d: int, k: int) -> tuple:
    """(operations, least bytes) of ``N`` float32 Grams of ``(d, k)``."""
    return 2 * N * d * k * k, F32 * N * (d * k + k * k)


def apply_calls(groups: dict, rank: int) -> list:
    """One step's low-rank apply calls ``base * G + U diag(c) U^T G``:
    ``(N, d, ell, n)`` for ``U (N, d, ell)`` and ``G (N, d, n)``; left then
    right side of every block."""
    out = []
    for (bm, bn), N in sorted(groups.items()):
        out.append((N, bm, min(rank, bm), bn))
        out.append((N, bn, min(rank, bn), bm))
    return out


def apply_cost(N: int, d: int, ell: int, n: int) -> tuple:
    """(operations, least bytes): ``U^T G`` and ``U (c * .)``, each
    ``2 N d ell n``; reads ``U``, ``G``, ``c`` and ``base``, writes the
    float32 output."""
    return 4 * N * d * ell * n, F32 * N * (d * ell + 2 * d * n + ell + 1)


def least_seconds(cost: tuple, peak: dict) -> tuple:
    """(seconds, bound): the larger of operations over the bf16 peak and
    bytes over the HBM bandwidth, and which of the two it is."""
    t_math = cost[0] / peak["bf16_flops"]
    t_mem = cost[1] / peak["hbm_bytes_per_s"]
    return (t_math, "compute") if t_math >= t_mem else (t_mem, "memory")
