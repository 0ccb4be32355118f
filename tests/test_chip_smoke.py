"""chip_smoke.py rehearsed on the CPU at tiny sizes.

The phase functions are the ones the chip runs, served through the same
dispatcher, engine, lanes and kernels (Pallas in interpret mode here); only
the sizes shrink.  ``main`` itself must refuse the CPU.  The four-chip
phase runs in a child process with four virtual CPU devices.
"""
import importlib.util
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _lines(out):
    recs = [json.loads(line) for line in out]
    for r in recs:
        assert r["mape"] <= 1e-4 and r["device_kind"] == "cpu"
    return recs


def test_main_refuses_cpu(smoke, capsys):
    assert smoke.main([]) == 2
    out, err = capsys.readouterr()
    assert out == "" and "no TPU" in err


def test_gaussian_is_seeded(smoke):
    a = smoke.gaussian(3, (64, 8))
    assert a.dtype == "float32"
    assert (a == smoke.gaussian(3, (64, 8))).all()
    assert not (a == smoke.gaussian(4, (64, 8))).all()


@pytest.mark.parametrize("phase,kw,path", [
    ("phase_tall", dict(obs_=512, nvars=64, k=4), "xla"),
    ("phase_fused", dict(obs_=2048, nvars=(64, 128, 32), k=(2, 4, 1)),
     "fused"),
    ("phase_stream", dict(obs_=4096, nvars=512, k=1), "stream"),
    ("phase_tenants", dict(obs_=256, nvars=32, designs=2, tenants=2),
     "xla"),
])
def test_phase_tiny(smoke, phase, kw, path):
    recs = _lines(getattr(smoke, phase)("cpu", 0, **kw))
    assert {r["path"] for r in recs} == {path}
    if phase == "phase_tenants":
        assert recs[0]["served"] == 4


def test_phase_fails_on_a_fallback(smoke, monkeypatch):
    """A fused request that falls back to XLA (here: a VMEM budget too small
    for it) is served correctly, and the phase still fails."""
    cd = sys.modules["repro.kernels.cd_sweep"]
    monkeypatch.setattr(cd, "VMEM_BUDGET_BYTES", 1024)
    with pytest.raises(smoke.PhaseError, match="expected path 'fused'"):
        smoke.phase_fused("cpu", 0, obs_=2048, nvars=(64,), k=(2,))


def test_phase_sharded_four_virtual_devices():
    script = textwrap.dedent(f"""
        import os, sys, importlib.util
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, {str(ROOT / "src")!r})
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        from repro.serve import build_serve_mesh
        smoke.phase_sharded("cpu", 0, build_serve_mesh("4"), obs_=4096,
                            nvars=64, k=2, rhs_obs=512, rhs_vars=64,
                            rhs_k=8)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stdout + "\n" + p.stderr
    recs = [json.loads(line) for line in p.stdout.splitlines()]
    assert [r["phase"] for r in recs] == ["s_obs_sharded", "s_rhs_sharded"]
    assert recs[0]["shard_shape"] == [1024, 64]
    assert len(recs[0]["bytes_in_use"]) == 4
    assert recs[1]["max_rel_diff_vs_single"] <= 1e-4
