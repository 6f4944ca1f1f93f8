"""Random weights for a dense GQA decoder, made on the device from the
seed in one jitted call, in the served dtype.

The tree has the layout the served program takes (``models/model.py``):
``embed`` (V, d), ``final_norm`` (d,), ``lm_head`` (d, V) when the head
is untied, and ``body`` = one block dict whose leaves are stacked over
the layers: ``norm1``, ``norm2`` (L, d); ``attn`` with ``wq`` (L, d,
h*hd), ``wk``/``wv`` (L, d, kvh*hd), ``wo`` (L, h*hd, d) and, with a
qkv bias, ``bq``/``bk``/``bv``; ``mlp`` with ``wi`` (L, d, 2*ff) holding
[gate | up] and ``wo`` (L, ff, d).  The plain reference reads the same
tree.  Each layer (and each block of embedding rows) is drawn in float32
and cast, one at a time under ``lax.map``, so the peak stays near the
size of the weights themselves.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

ROW_BLOCKS = 8      # the embedding and the head are drawn in this many blocks


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _layer(key, m: dict, dtype):
    d, h, kvh, hd, ff = (m["d"], m["h"], m["kvh"], m["hd"], m["ff"])
    k = jax.random.split(key, 11)
    attn = {"wq": _normal(k[0], (d, h * hd), d ** -0.5, dtype),
            "wk": _normal(k[1], (d, kvh * hd), d ** -0.5, dtype),
            "wv": _normal(k[2], (d, kvh * hd), d ** -0.5, dtype),
            "wo": _normal(k[3], (h * hd, d), (h * hd) ** -0.5, dtype)}
    if m["qkv_bias"]:
        attn["bq"] = _normal(k[4], (h * hd,), 0.1, dtype)
        attn["bk"] = _normal(k[5], (kvh * hd,), 0.1, dtype)
        attn["bv"] = _normal(k[6], (kvh * hd,), 0.1, dtype)
    return {"norm1": (1.0 + _normal(k[7], (d,), 0.1, jnp.float32)
                      ).astype(dtype),
            "attn": attn,
            "norm2": (1.0 + _normal(k[8], (d,), 0.1, jnp.float32)
                      ).astype(dtype),
            "mlp": {"wi": _normal(k[9], (d, 2 * ff), d ** -0.5, dtype),
                    "wo": _normal(k[10], (ff, d), ff ** -0.5, dtype)}}


def _rows(key, rows: int, cols: int, scale, dtype):
    """(rows, cols) drawn in ROW_BLOCKS blocks of rows."""
    keys = jax.random.split(key, ROW_BLOCKS)
    blk = -(-rows // ROW_BLOCKS)
    out = jax.lax.map(lambda k: _normal(k, (blk, cols), scale, dtype), keys)
    return out.reshape(ROW_BLOCKS * blk, cols)[:rows]


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, frozen_model):
    m = dict(frozen_model)
    dtype = jnp.dtype(m["dtype"])
    d, v = m["d"], m["vocab"]
    k_emb, k_head, k_norm, k_body = jax.random.split(key, 4)
    params = {"embed": _rows(k_emb, v, d, d ** -0.5, dtype),
              "final_norm": (1.0 + _normal(k_norm, (d,), 0.1, jnp.float32)
                             ).astype(dtype),
              "prefix": (), "suffix": ()}
    if not m["tied"]:
        # drawn as (V, d) rows, stored (d, V) as the program keeps it
        params["lm_head"] = _rows(k_head, v, d, d ** -0.5, dtype).T
    layer_keys = jax.random.split(k_body, m["layers"])
    params["body"] = (jax.lax.map(lambda k: _layer(k, m, dtype),
                                  layer_keys),)
    return params


def model_dims(model: dict) -> dict:
    """The sizes ``make_params`` and the reference read, from a
    configuration file's ``model`` section (Hugging Face key names)."""
    h = model["num_attention_heads"]
    return {"d": model["hidden_size"], "h": h,
            "kvh": model["num_key_value_heads"],
            "hd": model.get("head_dim") or model["hidden_size"] // h,
            "ff": model["intermediate_size"],
            "vocab": model["vocab_size"],
            "layers": model["num_hidden_layers"],
            "qkv_bias": bool(model.get("attention_bias", False)),
            "tied": bool(model["tie_word_embeddings"]),
            "rope_theta": float(model["rope_theta"]),
            "eps": float(model["rms_norm_eps"]),
            "dtype": model["torch_dtype"]}


def make_params(seed32: int, dims: dict):
    """The whole tree on the default device, from a 31-bit seed."""
    frozen = tuple(sorted(dims.items()))
    return _make(jax.random.PRNGKey(seed32), frozen)
