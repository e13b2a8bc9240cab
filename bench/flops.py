"""Operations and bytes, worked out from shapes: the model step and each
attention kernel call. Nothing here reads the system under test.

A config ``c`` is a configuration file's dict (``hidden_size``, ...). What
depends on the architecture (the model step's operations, how many layers
call the attention kernels) is its layout's (``layouts/<name>.py``),
found by the config's ``layout`` under ``root``; a kernel call's counts
depend on the attention widths only and stay here.
"""
from __future__ import annotations

import json
import os

import lookup

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")
BF16 = 2
F32 = 4


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r} in {PEAKS_FILE}")
    return table[device_kind]


def causal_pairs(s: int) -> float:
    """(query, key) pairs of a causal prompt of ``s`` tokens."""
    return s * (s + 1) / 2.0


def model_flops(c: dict, s: int, root: str = lookup.BENCH) -> float:
    """Useful operations of one prefill of ``s`` tokens, as the config's
    layout counts them."""
    return lookup.layout(c, root).model_flops(c, s)


def self_kernel(c: dict, chunk: int, batch: int = 1):
    """(flops, bytes) of one causal self-block call for one layer: the
    chunk's queries against its own keys. Reads q, k, v in bfloat16;
    writes the normalised output (bfloat16), the float32 accumulator and
    the float32 row max and sum."""
    h, kvh, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    flops = 4.0 * h * hd * causal_pairs(chunk) * batch
    rd = batch * chunk * (h + 2 * kvh) * hd * BF16
    wr = batch * chunk * h * (hd * BF16 + hd * F32 + 2 * F32)
    return flops, float(rd + wr)


def pool_kernel(c: dict, chunk: int, prefix: int, batch: int = 1):
    """(flops, bytes) of one pool call for one layer: the chunk's queries
    against ``prefix`` earlier tokens read from the page store. Reads q and
    each stored page once; writes the float32 accumulator, max and sum."""
    h, kvh, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    flops = 4.0 * h * hd * chunk * prefix * batch
    rd = batch * (chunk * h * hd + 2 * prefix * kvh * hd) * BF16
    wr = batch * chunk * h * (hd * F32 + 2 * F32)
    return flops, float(rd + wr)


def kernel_min_seconds(c: dict, seq: int, num_chunks: int, peak: dict,
                       batch: int = 1, root: str = lookup.BENCH) -> dict:
    """Least time of every attention kernel call of one prefill of ``seq``
    tokens in ``num_chunks`` chunks, over the layers that attend (the
    config's layout counts them), by kernel: ``{"self": s, "pool": s}``. A
    call's least time is the larger of its operations over the peak rate
    and its bytes over the memory bandwidth. Chunk 0 has no pool call with
    work in it."""
    chunk = seq // num_chunks
    calls = {"self": [self_kernel(c, chunk, batch)] * num_chunks,
             "pool": [pool_kernel(c, chunk, j * chunk, batch)
                      for j in range(1, num_chunks)]}
    layers = lookup.layout(c, root).attention_layers(c)
    return {name: layers * sum(
        max(fl / peak["bf16_flops_per_s"], by / peak["hbm_bytes_per_s"])
        for fl, by in cs) for name, cs in calls.items()}


def share(least_s: float, spent_s: float) -> float:
    """``least_s / spent_s`` as a fraction of a roofline or a peak. Above 1
    the operations or bytes were counted too high, or the time leaves out
    part of the work: that is an error, never a reading."""
    if spent_s <= 0:
        raise ValueError(f"no time spent ({spent_s!r} s)")
    s = least_s / spent_s
    if s > 1.0:
        raise ValueError(f"share {s!r} above 1: least time {least_s!r} s "
                         f"over {spent_s!r} s spent")
    return s
