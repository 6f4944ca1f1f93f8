"""Mesh-level disaggregation + launch-layer tests.

The KV-handoff correctness test executes in a SUBPROCESS with 8 forced
host devices (the parent process must keep seeing 1 device), building a
(pod=2, data=2, model=2) mesh and verifying pod0's prefilled KV actually
lands on pod1 through the collective_permute — the paper's KV transfer
as an ICI collective.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.launch import hlo_cost as H
from repro.launch.specs import input_specs, resolve_config
from repro.configs import get_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_kv_handoff_moves_cache_pod0_to_pod1():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses, json
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke_config
        from repro.core.disagg import kv_handoff
        from repro.models import model as M

        cfg = dataclasses.replace(get_smoke_config("qwen2_0_5b"),
                                  dtype="float32")
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        toks = jnp.ones((2, 8), jnp.int32)
        cache = M.init_cache(cfg, 2, 16)
        _, cache = M.prefill(params, cfg, toks, cache)
        with mesh:
            # place the cache with pod-replicated leaves; pods hold copies
            moved = kv_handoff(cache, mesh, batch_axes=("data",))
        # after the permute pod1 holds pod0's (identical) copy and pod0
        # holds zeros (ppermute with no inbound edge)
        k = moved["body"][0]["k"]
        per_pod = []
        for pod in range(2):
            # addressable shards: pick one device in each pod row
            arrs = [s.data for s in k.addressable_shards
                    if s.device.id in ((0,1,2,3) if pod==0 else (4,5,6,7))]
            total = sum(float(jnp.abs(a).sum()) for a in arrs)
            per_pod.append(total)
        orig = float(jnp.abs(cache["body"][0]["k"]).sum())
        print(json.dumps({"pod0": per_pod[0], "pod1": per_pod[1],
                          "orig_nonzero": orig > 0}))
    """)
    # pinned to the CPU: the child must never reach for an accelerator
    # that this (or any other) process may hold
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["orig_nonzero"]
    assert res["pod1"] > 0.0          # the KV arrived on the decode pod
    assert res["pod0"] == 0.0         # ownership transferred (one-sided put)


# ---------------------------------------------------------------------------
# launch/hlo_cost static analyzer
# ---------------------------------------------------------------------------
FAKE_HLO = """\
HloModule test

%body.1 (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %w = f32[8,8]{1,0} constant(...)
  %d = f32[8,8]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,8]{1,0} all-reduce(%d), replica_groups={}, to_apply=%add
  ROOT %t = (s32[], f32[8,8]) tuple(%i, %ar)
}

%cond.1 (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]) parameter(0)
  ROOT %lt = pred[] constant(true)
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %init = (s32[], f32[8,8]) tuple(...)
  %w2 = (s32[], f32[8,8]) while(%init), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"10"}}
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%w2), index=1
}
"""


def test_hlo_cost_weights_while_bodies_by_trip_count():
    s = H.analyze(FAKE_HLO)
    # dot: 2*8*8*8 = 1024 flops, x10 trips
    assert s.flops == pytest.approx(10 * 2 * 8 * 8 * 8)
    # all-reduce: 8*8*4 bytes x10 trips
    assert s.collective_bytes["all-reduce"] == pytest.approx(10 * 256)
    assert s.collective_counts["all-reduce"] == 10
    # link bytes apply the 2x ring factor for all-reduce
    assert s.link_bytes() == pytest.approx(2 * 10 * 256)
    assert s.unknown_trip_loops == 0


def test_hlo_tensor_bytes_parsing():
    assert H.tensor_bytes("f32[2,3]{1,0}") == 24
    assert H.tensor_bytes("bf16[10]") == 20
    assert H.tensor_bytes("(f32[2], s32[4])") == 8 + 16
    assert H.tensor_bytes("pred[]") == 1


# ---------------------------------------------------------------------------
# launch/specs: shape resolution carve-outs
# ---------------------------------------------------------------------------
def test_long_500k_resolution_rules():
    # whisper: skipped (learned-pos ctx limit)
    assert resolve_config(get_config("whisper_tiny"), "long_500k") is None
    # dense: sliding-window variant
    c = resolve_config(get_config("mistral_nemo_12b"), "long_500k")
    assert c is not None and c.sliding_window == 4096
    # VLM cross-attn arch also gets the window (self-attn is quadratic)
    c = resolve_config(get_config("llama_3_2_vision_11b"), "long_500k")
    assert c is not None and c.sliding_window == 4096
    # SSM/hybrid: native, unchanged
    c = resolve_config(get_config("xlstm_1_3b"), "long_500k")
    assert c is not None and c.sliding_window == 0
    c = resolve_config(get_config("recurrentgemma_9b"), "long_500k")
    assert c is not None and c.sliding_window == 0


def test_input_specs_shapes():
    import jax.numpy as jnp
    cfg = get_config("qwen2_0_5b")
    sp = input_specs(cfg, "train_4k")
    assert sp["tokens"].shape == (256, 4096)
    sp = input_specs(cfg, "decode_32k")
    assert sp["tokens"].shape == (128, 1)
    assert sp["pos"].shape == (128,)
    # VLM gets the stub frontend spec
    vcfg = get_config("llama_3_2_vision_11b")
    sp = input_specs(vcfg, "train_4k")
    assert sp["enc_embeds"].shape == (256, 1600, 4096)


def test_dryrun_results_cover_all_40_pairs():
    """The committed sweep results must cover 10 archs x 4 shapes x 2
    meshes with ok/skipped status only."""
    import glob
    recs = [json.load(open(f))
            for f in glob.glob(os.path.join(REPO, "results/dryrun/*.json"))]
    if not recs:
        pytest.skip("sweep results not present")
    seen = {(r["arch"], r["shape"], r["mesh"]) for r in recs}
    assert len(seen) == 80
    bad = [r for r in recs if r.get("status") not in ("ok", "skipped")]
    assert not bad, [(r["arch"], r["shape"]) for r in bad]
    skips = [r for r in recs if r.get("status") == "skipped"]
    assert {(r["arch"], r["shape"]) for r in skips} == {
        ("whisper_tiny", "long_500k")}


# ---------------------------------------------------------------------------
# launch/compile_cache: where the persistent compilation cache lives
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins and is left to JAX; otherwise the
    fixed, git-ignored directory at the root of the checkout."""
    import jax

    from repro.launch import compile_cache
    updates = []
    # record instead of turning the cache on: tests never enable it
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    got = compile_cache.enable_compile_cache()
    if env_dir is None:
        want = os.path.join(REPO, ".jax_cache")
        assert got == want
        assert updates == [("jax_compilation_cache_dir", want)]
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    else:
        assert got == env_dir and updates == []
