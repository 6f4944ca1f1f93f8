"""Plain reference of a dense GQA decoder (Qwen2, Mistral-Nemo): the
published layer equations in straightforward ``jax.numpy``, float32,
matrix products at ``Precision.HIGHEST``.  No kernel, cache, paging or
batching of requests: one whole sequence at a time, causal attention
over all of it, layer by layer so that it fits beside the weights.

Per layer (pre-norm):  h = x + Attn(RMSNorm(x));  y = h + MLP(RMSNorm(h))
  RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w
  Attn: q = x Wq + bq, k = x Wk + bk, v = x Wv + bv (bias when the
        configuration has one); RoPE on q and k, rotating the two halves
        of each head (theta from the configuration); softmax(q k^T /
        sqrt(hd)) v with each group of h/kvh query heads sharing one K/V
        head; output through Wo.
  MLP:  (silu(x Wg) * (x Wu)) Wd, with Wi = [Wg | Wu].
logits = RMSNorm(x_L) E^T (tied) or RMSNorm(x_L) W_head.

``mode="fp8"`` is the control: every matrix product takes its operands
rounded to float8 e4m3 (one scale per tensor, amax to 448) and adds in
float32.  It imports nothing of the served program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
HEAD_ROWS = 256
FP8_MAX = 448.0


def _q8(x):
    """Round to float8 e4m3 with a per-tensor scale, back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, mode: str, eq: str = None):
    if mode == "fp8":
        a, b = _q8(a), _q8(b)
    if eq is None:
        return jnp.matmul(a, b, precision=HI)
    return jnp.einsum(eq, a, b, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (T, heads, hd); rotate the two halves of each head."""
    t, _, hd = x.shape
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _layer(x, body, i, *, dims, mode):
    m = dict(dims)
    h, kvh, hd = m["h"], m["kvh"], m["hd"]
    p = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False)
        .astype(jnp.float32), body[0])
    t = x.shape[0]
    a = p["attn"]
    n = _rms(x, p["norm1"], m["eps"])
    q, k, v = _mm(n, a["wq"], mode), _mm(n, a["wk"], mode), \
        _mm(n, a["wv"], mode)
    if m["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q.reshape(t, h, hd), m["rope_theta"])
    k = _rope(k.reshape(t, kvh, hd), m["rope_theta"])
    v = v.reshape(t, kvh, hd)
    rep = h // kvh
    qg = q.reshape(t // Q_BLOCK, Q_BLOCK, kvh, rep, hd)
    kpos = jnp.arange(t)

    def block(args):
        qb, b = args
        s = _mm(qb, k, mode, "qgrd,kgd->grqk") * hd ** -0.5
        qpos = b * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return _mm(w, v, mode, "grqk,kgd->qgrd")

    att = jax.lax.map(block, (qg, jnp.arange(t // Q_BLOCK)))
    x = x + _mm(att.reshape(t, h * hd), a["wo"], mode)
    n = _rms(x, p["norm2"], m["eps"])
    g, u = jnp.split(_mm(n, p["mlp"]["wi"], mode), 2, axis=-1)
    return x + _mm(jax.nn.silu(g) * u, p["mlp"]["wo"], mode)


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _head(rows, params, targets, *, dims, mode):
    """rows: (HEAD_ROWS, d) final hidden states; targets: (k, HEAD_ROWS)
    token ids.  Returns (max logit, argmax, logits at targets)."""
    m = dict(dims)
    n = _rms(rows, params["final_norm"].astype(jnp.float32), m["eps"])
    w = (params["embed"].astype(jnp.float32).T if m["tied"]
         else params["lm_head"].astype(jnp.float32))
    logits = _mm(n, w, mode)
    at = jnp.take_along_axis(logits[None], targets[..., None], -1)[..., 0]
    return logits.max(-1), jnp.argmax(logits, -1).astype(jnp.int32), at


def _pad_len(t: int) -> int:
    return max(Q_BLOCK, 1 << (t - 1).bit_length())


def head_stats(params, dims: dict, seqs, starts, targets, mode="f32"):
    """Logit statistics of each sequence at positions ``starts[i]`` ..
    ``len(seqs[i]) - 1``: the logits there score the next token.

    seqs: token id arrays (prompt + served tokens, the last one dropped);
    targets: per sequence, a (k, n_i) array of token ids to read.
    Returns per sequence (max (n_i,), argmax (n_i,), at targets (k, n_i)).
    """
    frozen = tuple(sorted(dims.items()))
    embed = params["embed"]
    xs = []
    for s in seqs:
        t = _pad_len(len(s))
        tok = np.zeros(t, np.int32)
        tok[:len(s)] = s
        xs.append(jnp.take(embed, jnp.asarray(tok), axis=0)
                  .astype(jnp.float32))
    for i in range(dims["layers"]):
        xs = [_layer(x, params["body"], i, dims=frozen, mode=mode)
              for x in xs]
    out = []
    for x, s, st, tg in zip(xs, seqs, starts, targets):
        n = len(s) - st
        mx, am, at = [], [], []
        for b in range(0, n, HEAD_ROWS):
            rows = x[st + b: st + min(n, b + HEAD_ROWS)]
            k = rows.shape[0]
            rows = jnp.pad(rows, ((0, HEAD_ROWS - k), (0, 0)))
            tgt = np.zeros((tg.shape[0], HEAD_ROWS), np.int32)
            tgt[:, :k] = tg[:, b:b + k]
            r = _head(rows, params, jnp.asarray(tgt), dims=frozen,
                      mode=mode)
            mx.append(np.asarray(r[0])[:k])
            am.append(np.asarray(r[1])[:k])
            at.append(np.asarray(r[2])[:, :k])
        out.append((np.concatenate(mx), np.concatenate(am),
                    np.concatenate(at, axis=1)))
    return out
