"""Main-path kernels and serving steps compiled for a described TPU v5e.

Interpret mode (every other kernel test) cannot see Mosaic's tiling
rules or its VMEM limit.  These tests compile with ``interpret=False``
at published widths in bf16 for one chip of a ``v5e:2x2`` topology that
is described, not attached: nothing runs, so they need no chip.  The
topology is described inside a fixture, never at import time, and the
persistent compilation cache is off around the compiles.
"""
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.paged_cross_decode_attention import (
    paged_cross_decode_attention)
from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.kernels.paged_mla_decode_attention import paged_mla_decode_attention
from repro.kernels.paged_prefill_attention import paged_prefill_attention
from repro.models import model as M

PAGE = 16
N_PAGES = 2048 + 1          # a 2048-page pool plus the scratch page
SLOTS = 2048 // PAGE        # block-table width at max_seq 2048
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs on disk
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def sds(one_chip):
    """Shapes on the described chip: a compile needs no arrays."""
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _gqa(arch):
    cfg = get_config(arch)
    return cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim


@pytest.mark.parametrize("block_q", [16, 128])
@pytest.mark.parametrize("arch", ["qwen2_0_5b", "mistral_nemo_12b"])
def test_paged_prefill_compiles(sds, arch, block_q):
    h, kvh, hd = _gqa(arch)
    segs, sq = 4, 512
    c = _compile(
        lambda q, k, v, bt, kl, qo: paged_prefill_attention(
            q, k, v, bt, kl, qo, block_q=block_q, interpret=False),
        sds((segs, sq, h, hd), BF16), sds((N_PAGES, PAGE, kvh, hd), BF16),
        sds((N_PAGES, PAGE, kvh, hd), BF16), sds((segs, SLOTS), I32),
        sds((segs,), I32), sds((segs,), I32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("arch", ["qwen2_0_5b", "mistral_nemo_12b"])
def test_paged_decode_compiles(sds, arch):
    h, kvh, hd = _gqa(arch)
    b = 16
    c = _compile(
        lambda q, k, v, bt, ln: paged_decode_attention(
            q, k, v, bt, ln, interpret=False),
        sds((b, h, hd), BF16), sds((N_PAGES, PAGE, kvh, hd), BF16),
        sds((N_PAGES, PAGE, kvh, hd), BF16), sds((b, SLOTS), I32),
        sds((b,), I32))
    assert "tpu_custom_call" in c.as_text()


def test_paged_mla_decode_compiles(sds):
    cfg = get_config("deepseek_v2_236b")
    m, h = cfg.mla, cfg.n_heads
    b = 16
    # the model feeds W_uk-absorbed f32 queries against bf16 latent pages
    c = _compile(
        lambda ql, qr, ckv, kr, bt, ln: paged_mla_decode_attention(
            ql, qr, ckv, kr, bt, ln, scale=0.07, interpret=False),
        sds((b, h, m.kv_lora_rank), F32),
        sds((b, h, m.qk_rope_head_dim), F32),
        sds((N_PAGES, PAGE, m.kv_lora_rank), BF16),
        sds((N_PAGES, PAGE, m.qk_rope_head_dim), BF16),
        sds((b, SLOTS), I32), sds((b,), I32))
    assert "tpu_custom_call" in c.as_text()


def test_paged_cross_decode_compiles(sds):
    cfg = get_config("whisper_tiny")
    h, kvh, hd = _gqa("whisper_tiny")
    b, cross_slots = 16, -(-cfg.cross_ctx // PAGE)
    c = _compile(
        lambda q, k, v, bt, ln: paged_cross_decode_attention(
            q, k, v, bt, ln, interpret=False),
        sds((b, h, hd), BF16), sds((N_PAGES, PAGE, kvh, hd), BF16),
        sds((N_PAGES, PAGE, kvh, hd), BF16), sds((b, cross_slots), I32),
        sds((b,), I32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_qwen2_serving_step_compiles(sds, monkeypatch, step):
    """The engines' whole 24-layer paged steps: a Mosaic call inside, and
    the donated pools aliased — no copy of the pool per step."""
    # ops picks interpret mode from the backend it sees (the CPU here)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = get_config("qwen2_0_5b")
    params = jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype),
                                    M.abstract_params(cfg))
    pool = sds((cfg.n_layers, N_PAGES, PAGE, cfg.n_kv_heads,
                cfg.resolved_head_dim), jnp.dtype(cfg.dtype))
    if step == "prefill":
        ns, sq = 2, 256
        c = jax.jit(
            lambda p, t, qo, kl, la, bt, pg, of, kp, vp: M.prefill_paged(
                p, cfg, t, qo, kl, la, bt, pg, of, kp, vp),
            donate_argnums=(8, 9)).lower(
            params, sds((ns, sq), I32), sds((ns,), I32), sds((ns,), I32),
            sds((ns,), I32), sds((ns, SLOTS), I32), sds((ns, sq), I32),
            sds((ns, sq), I32), pool, pool).compile()
    else:
        b = 8
        c = jax.jit(
            lambda p, t, pos, pg, of, bt, ln, kp, vp: M.decode_step_paged(
                p, cfg, t, pos, pg, of, bt, ln, kp, vp),
            donate_argnums=(7, 8)).lower(
            params, sds((b, 1), I32), sds((b,), I32), sds((b,), I32),
            sds((b,), I32), sds((b, SLOTS), I32), sds((b,), I32), pool,
            pool).compile()
    assert "tpu_custom_call" in c.as_text()
    mem = c.memory_analysis()
    pool_bytes = 2 * math.prod(pool.shape) * pool.dtype.itemsize
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 8
