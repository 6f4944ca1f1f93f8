"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def _mk(shape, dtype, k):
    return jax.random.normal(jax.random.fold_in(KEY, k), shape, jnp.float32
                             ).astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,sq,h,kvh,hd,skv,bq,bk", [
    (1, 64, 4, 4, 64, 256, 32, 64),      # MHA
    (2, 128, 8, 2, 64, 512, 64, 128),    # GQA
    (2, 64, 4, 1, 128, 256, 64, 256),    # MQA, 128 head dim
    (1, 128, 4, 2, 32, 128, 128, 128),   # single kv block
])
def test_chunked_prefill_attention_sweep(dtype, b, sq, h, kvh, hd, skv,
                                         bq, bk):
    q = _mk((b, sq, h, hd), dtype, 1)
    k = _mk((b, skv, kvh, hd), dtype, 2)
    v = _mk((b, skv, kvh, hd), dtype, 3)
    q_off = jnp.array([skv - sq], jnp.int32)
    kv_len = jnp.array([skv] + [skv // 2] * (b - 1), jnp.int32)
    out = ops.prefill_attention(q, k, v, kv_len, q_off, block_q=bq,
                                block_kv=bk)
    exp = ref.ref_chunked_prefill_attention(q, k, v, kv_len, q_off)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    assert out.shape == exp.shape
    assert not bool(jnp.isnan(out.astype(jnp.float32)).any())
    assert float(jnp.abs(out.astype(jnp.float32)
                         - exp.astype(jnp.float32)).max()) < tol


@pytest.mark.parametrize("window", [0, 37, 128])
def test_chunked_prefill_attention_window(window):
    b, sq, h, kvh, hd, skv = 2, 64, 4, 2, 64, 256
    q = _mk((b, sq, h, hd), jnp.float32, 4)
    k = _mk((b, skv, kvh, hd), jnp.float32, 5)
    v = _mk((b, skv, kvh, hd), jnp.float32, 6)
    q_off = jnp.array([192], jnp.int32)
    kv_len = jnp.array([256, 200], jnp.int32)
    out = ops.prefill_attention(q, k, v, kv_len, q_off, window=window,
                                block_q=32, block_kv=64)
    exp = ref.ref_chunked_prefill_attention(q, k, v, kv_len, q_off,
                                            window=window)
    assert float(jnp.abs(out - exp).max()) < 2e-5


def test_chunked_prefill_mid_prompt_chunk():
    """Chunk in the middle of a prompt: cache has earlier tokens."""
    b, sq, h, kvh, hd, skv = 1, 32, 4, 4, 64, 128
    q = _mk((b, sq, h, hd), jnp.float32, 7)
    k = _mk((b, skv, kvh, hd), jnp.float32, 8)
    v = _mk((b, skv, kvh, hd), jnp.float32, 9)
    q_off = jnp.array([64], jnp.int32)     # tokens 64..96
    kv_len = jnp.array([96], jnp.int32)
    out = ops.prefill_attention(q, k, v, kv_len, q_off, block_q=32,
                                block_kv=64)
    exp = ref.ref_chunked_prefill_attention(q, k, v, kv_len, q_off)
    assert float(jnp.abs(out - exp).max()) < 2e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kvh,hd,npages,page,nslots", [
    (2, 4, 2, 64, 16, 64, 6),
    (1, 8, 8, 32, 8, 16, 8),      # MHA, small pages
    (4, 4, 1, 128, 32, 64, 4),    # MQA
])
def test_paged_decode_attention_sweep(dtype, b, h, kvh, hd, npages, page,
                                      nslots):
    q = _mk((b, h, hd), dtype, 10)
    kp = _mk((npages, page, kvh, hd), dtype, 11)
    vp = _mk((npages, page, kvh, hd), dtype, 12)
    bt = jax.random.randint(jax.random.fold_in(KEY, 13), (b, nslots), 0,
                            npages)
    maxlen = nslots * page
    lens = jax.random.randint(jax.random.fold_in(KEY, 14), (b,), 1,
                              maxlen + 1)
    out = ops.decode_attention(q, kp, vp, bt, lens)
    exp = ref.ref_paged_decode_attention(q, kp, vp, bt, lens)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    assert out.shape == exp.shape
    assert float(jnp.abs(out.astype(jnp.float32)
                         - exp.astype(jnp.float32)).max()) < tol


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,sq,h,kvh,hd,npages,page,nslots,bq", [
    (3, 32, 4, 2, 64, 10, 16, 4, 16),    # GQA, ragged offsets
    (1, 16, 4, 4, 32, 6, 16, 3, 16),     # MHA
    (2, 64, 8, 1, 64, 12, 32, 4, 32),    # MQA, bigger pages
])
def test_paged_prefill_attention_sweep(dtype, b, sq, h, kvh, hd, npages,
                                       page, nslots, bq):
    """The fused-chunk serving kernel: per-segment q_offset/kv_len over a
    block-table-addressed page pool."""
    q = _mk((b, sq, h, hd), dtype, 21)
    kp = _mk((npages, page, kvh, hd), dtype, 22)
    vp = _mk((npages, page, kvh, hd), dtype, 23)
    bt = jax.random.randint(jax.random.fold_in(KEY, 24), (b, nslots), 0,
                            npages)
    maxlen = nslots * page
    q_off = jax.random.randint(jax.random.fold_in(KEY, 25), (b,), 0,
                               maxlen - sq + 1)
    kv_len = jnp.minimum(q_off + sq, maxlen)
    out = ops.prefill_attention(q, kp, vp, kv_len, q_off, block_table=bt,
                                block_q=bq)
    exp = ref.ref_paged_prefill_attention(q, kp, vp, bt, kv_len, q_off)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    assert out.shape == exp.shape
    assert not bool(jnp.isnan(out.astype(jnp.float32)).any())
    assert float(jnp.abs(out.astype(jnp.float32)
                         - exp.astype(jnp.float32)).max()) < tol


def test_paged_prefill_matches_dense_prefill_kernel():
    """Paged and dense prefill kernels agree when the pool pages hold the
    same K/V the dense cache holds."""
    b, sq, h, kvh, hd, page, nslots = 2, 32, 4, 2, 64, 16, 4
    skv = nslots * page
    q = _mk((b, sq, h, hd), jnp.float32, 26)
    k = _mk((b, skv, kvh, hd), jnp.float32, 27)
    v = _mk((b, skv, kvh, hd), jnp.float32, 28)
    # lay the dense caches out in a pool: request i -> pages [4i, 4i+4)
    kp = k.reshape(b * nslots, page, kvh, hd)
    vp = v.reshape(b * nslots, page, kvh, hd)
    bt = jnp.arange(b * nslots, dtype=jnp.int32).reshape(b, nslots)
    q_off = jnp.array([skv - sq, 11], jnp.int32)
    kv_len = q_off + sq
    out_paged = ops.prefill_attention(q, kp, vp, kv_len, q_off,
                                      block_table=bt, block_q=16)
    # dense kernel takes a single shared q_offset -> compare per request
    for i in range(b):
        out_dense = ops.prefill_attention(
            q[i:i + 1], k[i:i + 1], v[i:i + 1], kv_len[i:i + 1],
            q_off[i:i + 1], block_q=16, block_kv=page)
        assert float(jnp.abs(out_paged[i] - out_dense[0]).max()) < 2e-5


# window edge cases: smaller than a page, exactly a page, spanning pages
@pytest.mark.parametrize("window", [3, 16, 21])
def test_paged_prefill_attention_window(window):
    """Windowed paged prefill: in-kernel kv-block skipping + window mask
    agree with the dense-gather oracle across page-boundary cases."""
    b, sq, h, kvh, hd, npages, page, nslots = 3, 16, 4, 2, 32, 12, 16, 4
    q = _mk((b, sq, h, hd), jnp.float32, 30)
    kp = _mk((npages, page, kvh, hd), jnp.float32, 31)
    vp = _mk((npages, page, kvh, hd), jnp.float32, 32)
    bt = jax.random.randint(jax.random.fold_in(KEY, 33), (b, nslots), 0,
                            npages)
    q_off = jnp.array([0, 17, 48], jnp.int32)   # incl. offset mid-page
    kv_len = q_off + sq
    out = ops.prefill_attention(q, kp, vp, kv_len, q_off, block_table=bt,
                                window=window, block_q=16)
    exp = ref.ref_paged_prefill_attention(q, kp, vp, bt, kv_len, q_off,
                                          window=window)
    assert not bool(jnp.isnan(out).any())
    assert float(jnp.abs(out - exp).max()) < 2e-5


@pytest.mark.parametrize("window", [3, 16, 21])
def test_paged_decode_attention_window(window):
    """Windowed paged decode: pages that slid wholly out of the window
    are skipped (their table slots may be scratch) and the token mask
    matches the oracle at page boundaries."""
    b, h, kvh, hd, npages, page, nslots = 4, 4, 2, 32, 12, 16, 4
    q = _mk((b, h, hd), jnp.float32, 34)
    kp = _mk((npages, page, kvh, hd), jnp.float32, 35)
    vp = _mk((npages, page, kvh, hd), jnp.float32, 36)
    bt = jax.random.randint(jax.random.fold_in(KEY, 37), (b, nslots), 0,
                            npages)
    # lens straddling page boundaries: window end mid-page / on-page-edge
    lens = jnp.array([5, 16, 33, 64], jnp.int32)
    out = ops.decode_attention(q, kp, vp, bt, lens, window=window)
    exp = ref.ref_paged_decode_attention(q, kp, vp, bt, lens,
                                         window=window)
    assert float(jnp.abs(out - exp).max()) < 2e-5


def test_paged_decode_window_ignores_slid_out_pages():
    """Out-of-window table slots may point at a garbage scratch page —
    the kernel must never let that page reach the softmax."""
    b, h, kvh, hd, npages, page = 1, 4, 2, 32, 4, 8
    q = _mk((b, h, hd), jnp.float32, 38)
    kp = _mk((npages, page, kvh, hd), jnp.float32, 39)
    vp = _mk((npages, page, kvh, hd), jnp.float32, 40)
    # request: 24 tokens over slots [0,1,2]; window 8 -> the query at
    # position 23 attends keys 16..23, so slots 0 AND 1 are dead
    bt_live = jnp.array([[0, 1, 2]], jnp.int32)
    bt_trash = jnp.array([[3, 3, 2]], jnp.int32)   # dead slots -> scratch
    lens = jnp.array([24], jnp.int32)
    out_live = ops.decode_attention(q, kp, vp, bt_live, lens, window=8)
    out_trash = ops.decode_attention(q, kp, vp, bt_trash, lens, window=8)
    assert float(jnp.abs(out_live - out_trash).max()) == 0.0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [0, 3, 16, 21])
def test_paged_mla_decode_attention_sweep(dtype, window):
    """Absorbed MLA decode over the paged latent pool vs dense-gather
    oracle, across window edge cases."""
    b, h, lora, rope, npages, page, nslots = 3, 4, 32, 16, 10, 16, 4
    ql = _mk((b, h, lora), dtype, 41)
    qr = _mk((b, h, rope), dtype, 42)
    cp = _mk((npages, page, lora), dtype, 43)
    krp = _mk((npages, page, rope), dtype, 44)
    bt = jax.random.randint(jax.random.fold_in(KEY, 45), (b, nslots), 0,
                            npages)
    lens = jnp.array([7, 16, 50], jnp.int32)
    scale = (lora + rope) ** -0.5
    out = ops.mla_decode_attention(ql, qr, cp, krp, bt, lens, scale=scale,
                                   window=window)
    exp = ref.ref_paged_mla_decode_attention(ql, qr, cp, krp, bt, lens,
                                             scale=scale, window=window)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    assert out.shape == exp.shape
    assert not bool(jnp.isnan(out.astype(jnp.float32)).any())
    assert float(jnp.abs(out.astype(jnp.float32)
                         - exp.astype(jnp.float32)).max()) < tol


def test_paged_decode_single_token_cache():
    """lens=1: only the first token of the first page is live."""
    q = _mk((1, 4, 64), jnp.float32, 15)
    kp = _mk((4, 16, 2, 64), jnp.float32, 16)
    vp = _mk((4, 16, 2, 64), jnp.float32, 17)
    bt = jnp.array([[2, 0]], jnp.int32)
    lens = jnp.array([1], jnp.int32)
    out = ops.decode_attention(q, kp, vp, bt, lens)
    exp = ref.ref_paged_decode_attention(q, kp, vp, bt, lens)
    assert float(jnp.abs(out - exp).max()) < 1e-5
    # attention over one token == that token's V
    v0 = vp[2, 0]  # (kvh, hd)
    expand = jnp.repeat(v0, 2, axis=0)
    assert float(jnp.abs(out[0] - expand).max()) < 1e-5


def test_kernel_matches_model_flash_attention():
    """Kernel path agrees with the model-substrate flash_attn."""
    from repro.models.attention import flash_attn
    b, sq, h, kvh, hd = 2, 64, 4, 2, 64
    q = _mk((b, sq, h, hd), jnp.float32, 18)
    k = _mk((b, sq, kvh, hd), jnp.float32, 19)
    v = _mk((b, sq, kvh, hd), jnp.float32, 20)
    out_model = flash_attn(q, k, v, causal=True)
    out_kernel = ops.prefill_attention(
        q, k, v, jnp.array([sq] * b, jnp.int32), jnp.array([0], jnp.int32),
        block_q=32, block_kv=32)
    assert float(jnp.abs(out_model - out_kernel).max()) < 2e-5


@pytest.mark.parametrize("backend,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", None)])
def test_interpret_mode_only_on_cpu(monkeypatch, backend, interpret):
    """Kernels run interpreted on the CPU and compile on a TPU; any other
    backend raises instead of silently interpreting."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    ops._interpret.cache_clear()
    try:
        if interpret is None:
            with pytest.raises(RuntimeError, match="gpu"):
                ops._interpret()
        else:
            assert ops._interpret() is interpret
    finally:
        ops._interpret.cache_clear()
