"""chip_smoke.py at toy size on the host: every phase's checks run on CPU
devices (Pallas in interpret mode, the mesh on forced host devices), and the
script itself refuses to report success without a TPU."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
_spec = importlib.util.spec_from_file_location("chip_smoke", REPO / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

TOY = dict(n_requests=6, prompt_lens=(4, 8), max_new_tokens=6, num_slots=4,
           cache_len=32)


@pytest.fixture(scope="module")
def toy_cfg():
    from repro.configs import get_smoke_config

    return get_smoke_config(chip_smoke.ARCH, sqrt_unit="e2afs")


def _run_script(tmp_cwd, env_overrides, *, script=REPO / "chip_smoke.py"):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_overrides)
    return subprocess.run([sys.executable, str(script)], cwd=tmp_cwd, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("env", [
    {"JAX_PLATFORMS": "cpu"},
    {"JAX_PLATFORMS": "cpu", "REPRO_KERNEL_BACKEND": "interpret"},
], ids=["no-tpu", "interpret-backend"])
def test_script_fails_without_the_chip(tmp_path, env):
    res = _run_script(tmp_path, env)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
    assert "FAIL" in res.stdout


def test_script_fails_outside_the_repo(tmp_path):
    script = tmp_path / "chip_smoke.py"
    shutil.copy(REPO / "chip_smoke.py", script)
    res = _run_script(tmp_path, {"JAX_PLATFORMS": "cpu"}, script=script)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout


@pytest.mark.parametrize("platform,env,error", [
    ("cpu", {}, "no TPU"),
    ("tpu", {"REPRO_KERNEL_BACKEND": "interpret"}, "REPRO_KERNEL_BACKEND"),
    ("tpu", {"REPRO_KERNEL_BACKEND": "reference"}, "REPRO_KERNEL_BACKEND"),
])
def test_check_device_refuses(platform, env, error):
    dev = SimpleNamespace(platform=platform, device_kind="x")
    with pytest.raises(chip_smoke.SmokeFailure, match=error):
        chip_smoke.check_device([dev], env)


def test_check_device_record():
    devs = [SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")] * 4
    rec = chip_smoke.check_device(devs, {"REPRO_KERNEL_BACKEND": "auto"})
    assert rec == {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}


def test_input_classes_and_nan_aware_mismatch():
    x = np.array([1.0, 0.0, -0.0, np.inf, np.nan, 1e-7, -2.0], np.float16)
    cls = chip_smoke.input_classes(x)
    assert cls["normal"].tolist() == [1, 0, 0, 0, 0, 0, 1]
    assert cls["subnormal"].tolist() == [0, 0, 0, 0, 0, 1, 0]
    assert cls["special"].tolist() == [0, 1, 1, 1, 1, 0, 0]
    nan2 = np.array([np.nan], np.float32)
    assert not chip_smoke.mismatches(nan2, -nan2).any()
    assert chip_smoke.mismatches(np.float32([1.0]), np.float32([-1.0])).all()


def test_datapath_phase_on_host():
    import jax

    host = jax.devices("cpu")[0]
    report = chip_smoke.datapath_phase(host, host, fp32_sample=4096)
    assert len(report) == 6
    assert all(counts["normal"] == 0 for counts in report.values())


def test_engine_phase_toy(toy_cfg):
    params = chip_smoke.init_params(toy_cfg)
    checks = chip_smoke.engine_phase(toy_cfg, params, n_solo=2, **TOY)
    assert len(checks) == 2
    assert all(c["exact"] for c in checks)


def test_slot_vs_solo_flags_a_wrong_token(toy_cfg):
    """A slot token that is not a near-tie of the solo logits must fail."""
    import jax.numpy as jnp

    from repro.launch.engine import solo_generate
    from repro.models import lm

    params = chip_smoke.init_params(toy_cfg)
    req = chip_smoke.make_requests(toy_cfg.vocab, n=1, prompt_lens=(5,),
                                   max_new_tokens=4)[0]
    solo = solo_generate(params, toy_cfg, req.prompt, 4, cache_len=16)
    cache, _ = lm.init_cache(toy_cfg, 1, 16)
    logits, _ = lm.prefill(params, toy_cfg, cache, jnp.asarray(req.prompt)[None],
                           last_logit_only=True)
    wrong = solo.copy()
    # the least likely first token is no near-tie of the most likely one
    wrong[0] = int(np.argmin(np.asarray(logits[0, -1], np.float32)))
    with pytest.raises(chip_smoke.SmokeFailure, match="near-tie"):
        chip_smoke.slot_vs_solo(params, toy_cfg, req, wrong, cache_len=16)


def test_apps_phase_toy():
    chip_smoke.apps_phase(size=64, require_kernel=False)


def test_mesh_phase_toy(toy_cfg):
    import jax

    assert jax.device_count() >= 4  # tests/conftest.py forces 4 host devices
    agree = chip_smoke.mesh_phase(toy_cfg, **TOY)
    assert agree["exact"] == TOY["n_requests"]
    assert 0 <= agree["tp"] <= TOY["n_requests"]
