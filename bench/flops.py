"""Operations and bytes of the served steps and of the two attention
kernels, from shapes alone (dense GQA decoder; ``dims`` as
``weights.model_dims`` gives them).  A multiply-add counts 2 FLOPs.
These count the work a request needs, not what a padded batch runs.
"""
from __future__ import annotations

import json
import os

CHUNK = 512


def matmul_flops_per_token(dims: dict) -> int:
    """Projections and MLP of every layer, per token."""
    d, h, kvh, hd, ff = (dims["d"], dims["h"], dims["kvh"], dims["hd"],
                         dims["ff"])
    per_layer = 2 * (d * h * hd + 2 * d * kvh * hd + h * hd * d
                     + 3 * d * ff)
    return dims["layers"] * per_layer


def head_flops(dims: dict) -> int:
    return 2 * dims["d"] * dims["vocab"]


def attn_flops(dims: dict, n_keys: int) -> int:
    """Scores and weighted sum of one query over ``n_keys`` keys, all
    layers."""
    return dims["layers"] * 4 * dims["h"] * dims["hd"] * n_keys


def prefill_attn_flops(dims: dict, prompt: int) -> int:
    """Causal attention of a whole prompt: query i sees i + 1 keys."""
    return dims["layers"] * 4 * dims["h"] * dims["hd"] \
        * prompt * (prompt + 1) // 2


def prefill_flops(dims: dict, prompt: int) -> int:
    """The model FLOPs a prompt needs: every token through every layer,
    causal attention, and the head once for its first token."""
    return (prompt * matmul_flops_per_token(dims)
            + prefill_attn_flops(dims, prompt) + head_flops(dims))


def decode_flops(dims: dict, context: int) -> int:
    """One decode token whose query sees ``context`` keys (itself
    included)."""
    return (matmul_flops_per_token(dims) + attn_flops(dims, context)
            + head_flops(dims))


def _kv_bytes_per_token(dims: dict, itemsize: int = 2) -> int:
    """K and V of one token in one layer."""
    return 2 * dims["kvh"] * dims["hd"] * itemsize


def prefill_attn_bytes(dims: dict, prompt: int, itemsize: int = 2) -> int:
    """Least HBM traffic of the paged prefill kernel for one prompt cut
    into CHUNK-token segments from its start: each segment reads the K/V
    of every key it sees once, and reads q and writes its output once."""
    total = 0
    for start in range(0, prompt, CHUNK):
        end = min(prompt, start + CHUNK)
        total += end * _kv_bytes_per_token(dims, itemsize) \
            + (end - start) * 2 * dims["h"] * dims["hd"] * itemsize
    return dims["layers"] * total


def decode_attn_bytes(dims: dict, context: int, itemsize: int = 2) -> int:
    """Least HBM traffic of the paged decode kernel for one slot: the
    K/V of ``context`` keys, plus q in and the output out."""
    return dims["layers"] * (context * _kv_bytes_per_token(dims, itemsize)
                             + 2 * dims["h"] * dims["hd"] * itemsize)


def peaks(bench_dir: str, device_kind: str) -> dict:
    """The chip's peaks; an unknown device kind is an error."""
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table[device_kind]
