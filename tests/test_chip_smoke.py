"""``chip_smoke.py``: it refuses to run without a TPU, and its phases pass
on the smoke config on the CPU (the rehearsal of the chip run)."""
import importlib.util
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _load(monkeypatch):
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, "chip_smoke", mod)   # dataclasses
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_without_tpu():
    # pinned to the CPU: the child must never reach for a chip
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, SCRIPT], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout == ""            # no device line, model or result
    assert "JAX found no TPU" in out.stderr


def test_chip_smoke_phases_rehearse_on_cpu(monkeypatch, capsys):
    smoke = _load(monkeypatch)
    smoke.run(smoke.REHEARSAL, seed=0, on_tpu=False)
    out = capsys.readouterr().out
    assert f"{smoke.N_REQUESTS} requests x {smoke.NEW_TOKENS} tokens " \
        "finished" in out
    assert f"identical to CoupledEngine for {smoke.N_REQUESTS}/" \
        f"{smoke.N_REQUESTS} requests" in out
