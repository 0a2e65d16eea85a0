"""Operations and bytes the algorithms require, from shapes alone.

These are the yardstick's counts: the work a computation needs, not the
steps a kernel happens to run. Bucket padding, padded rows and dense-grid
steps that skip do not count, so a share of a roofline reads the same
whatever implements the work.
"""
from __future__ import annotations

import math

import numpy as np

F32 = 4


def gated_gemm_cost(rows: int, k: int, n: int, tile: int, *, ii=None, jj=None,
                    kk=None, itemsize: int = F32) -> tuple:
    """(flops, bytes) of C = A @ B with A (rows, k), B (k, n), gated per
    (tile x tile x tile) triple.

    `ii, jj, kk` are the surviving triples' row, column and contraction
    tile ids; None means every triple survives (tau = 0). A row tile holds
    `tile` rows except the last, which holds what is left of `rows`: the
    real rows, not the padded ones. Bytes count each distinct A tile and
    B tile that some surviving triple reads, once, and the whole of C once
    (every output tile is written, zero where no triple survives)."""
    gm, gk, gn = math.ceil(rows / tile), k // tile, n // tile
    if ii is None:
        flops = 2.0 * rows * k * n
        bytes_ = itemsize * (rows * k + k * n + rows * n)
        return flops, float(bytes_)
    ii = np.asarray(ii, np.int64)
    jj = np.asarray(jj, np.int64)
    kk = np.asarray(kk, np.int64)
    tile_rows = np.full(gm, tile, np.int64)
    tile_rows[-1] = rows - tile * (gm - 1)
    flops = 2.0 * float(tile_rows[ii].sum()) * tile * tile
    a_tiles = np.unique(ii * gk + kk)
    b_tiles = np.unique(kk * gn + jj)
    a_bytes = float(tile_rows[a_tiles // gk].sum()) * tile
    b_bytes = float(b_tiles.size) * tile * tile
    bytes_ = itemsize * (a_bytes + b_bytes + rows * n)
    return flops, bytes_


def least_time_s(flops: float, bytes_: float, peaks: dict) -> tuple:
    """The least time the chip could take, and which bound sets it: the
    larger of operations over peak FLOP/s (bf16) and bytes over HBM
    bandwidth."""
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")


def transformer_matmul_params(cfg: dict) -> int:
    """Parameters of one decoder layer's GEMMs times the depth: q, k, v, o
    projections and the two FFN matrices (the gated GEMMs)."""
    d, h, kvh = cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"]
    hd = cfg["head_dim"]
    per_layer = d * h * hd + 2 * d * kvh * hd + h * hd * d + 2 * d * cfg["d_ff"]
    return cfg["num_layers"] * per_layer


def transformer_flops(cfg: dict, context: np.ndarray, logits_rows: int) -> float:
    """Dense model FLOPs of processing tokens that attend to `context`
    positions each (a token at position p sees p + 1), plus `logits_rows`
    rows of the unembedding: 2 x matmul parameters per token, and per
    layer 2 x 2 x context x (heads x head_dim) for the scores and the
    weighted sum."""
    context = np.asarray(context, np.float64)
    hq = cfg["num_heads"] * cfg["head_dim"]
    per_token = 2.0 * transformer_matmul_params(cfg)
    attn = 4.0 * hq * cfg["num_layers"] * float(context.sum())
    unembed = 2.0 * cfg["d_model"] * cfg["vocab"] * logits_rows
    return per_token * context.size + attn + unembed


def wave_flops(cfg: dict, batch: int, prompt_len: int, new_tokens: int) -> float:
    """FLOPs of one serving wave: the prompt's tokens (positions 0 ..
    prompt_len - 1, logits at the last), then new_tokens - 1 decode steps
    (each one token at the next position, with its logits)."""
    prompt_ctx = np.arange(1, prompt_len + 1)
    decode_ctx = prompt_len + np.arange(1, new_tokens)
    one = (transformer_flops(cfg, prompt_ctx, 1)
           + transformer_flops(cfg, decode_ctx, new_tokens - 1))
    return batch * one


def serve_gemm_shapes(cfg: dict, rows: int) -> list:
    """(rows, k, n) of the gated GEMMs one step runs over `rows` token rows:
    per layer wq, wk, wv, wo, w1, w2."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    hq, hk = cfg["num_heads"] * hd, cfg["num_kv_heads"] * hd
    one = [(rows, d, hq), (rows, d, hk), (rows, d, hk), (rows, hq, d),
           (rows, d, cfg["d_ff"]), (rows, cfg["d_ff"], d)]
    return one * cfg["num_layers"]
