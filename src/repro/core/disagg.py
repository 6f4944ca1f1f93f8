"""Mesh-level prefill/decode disaggregation (dry-run artifact).

The runtime disaggregates in *space*: separate prefill/decode instances
exchanging KV over the network stack (core/kv_transfer.py).  On the TPU
multi-pod mesh the equivalent first-class operation is a KV handoff
across the ``pod`` axis: prefill pod 0 produces the KV cache, a
``collective_permute`` (ppermute) ships every cache shard pod0 -> pod1
over ICI/DCI — the one-sided-put analogue — and the decode step consumes
it on pod 1.

``disagg_step`` composes chunked prefill + handoff + one decode step in a
single jit so the dry-run proves the whole pipeline (including the
cross-pod collective schedule) lowers and fits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.models import model as M
from repro.models import sharding as S
from repro.models.config import ModelConfig


def kv_handoff(cache, mesh: Mesh, batch_axes=("data",)):
    """Ship every cache leaf pod0 -> pod1 via collective_permute.

    Leaves keep their data/model sharding; only the pod placement moves.
    Returns the cache as seen by the decode pod (pod 1); pod 0's copy is
    zeros afterwards (ownership transferred, as in a one-sided put).
    """
    assert "pod" in mesh.axis_names, "kv_handoff needs a multi-pod mesh"
    model_size = mesh.shape.get("model", 1)

    def leaf_spec(path, leaf):
        sp = S.cache_spec(path, leaf, model_size=model_size,
                          batch_axes=batch_axes)
        return sp
    specs = jax.tree_util.tree_map_with_path(leaf_spec, cache)

    def body(*leaves):
        perm = [(0, 1)]
        return tuple(
            jax.lax.ppermute(l, "pod", perm) for l in leaves)

    flat, treedef = jax.tree_util.tree_flatten(cache)
    flat_specs = treedef.flatten_up_to(specs)
    out = jax.shard_map(body, mesh=mesh,
                        in_specs=tuple(flat_specs),
                        out_specs=tuple(flat_specs),
                        check_vma=False)(*flat)
    return jax.tree_util.tree_unflatten(treedef, list(out))


def make_disagg_step(cfg: ModelConfig, mesh: Mesh, *, chunk_size: int,
                     batch_axes=("data",)):
    """Build the jit-able disagg_step(params, tokens, cache, enc) ->
    (first_logits, decode_logits, cache): chunked prefill, pod0->pod1 KV
    handoff, one decode step."""

    def disagg_step(params, tokens, cache, enc_embeds=None):
        b, s = tokens.shape
        first_logits, cache = M.prefill_chunked(
            params, cfg, tokens, cache, chunk_size=chunk_size,
            enc_embeds=enc_embeds)
        cache = kv_handoff(cache, mesh, batch_axes=batch_axes)
        first_tok = jnp.argmax(first_logits[:, -1], axis=-1)[:, None]
        pos = jnp.full((b,), s, jnp.int32)
        dec_logits, cache = M.decode_step(params, cfg,
                                          first_tok.astype(jnp.int32),
                                          cache, pos)
        return first_logits, dec_logits, cache

    return disagg_step
