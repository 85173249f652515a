"""CPU rehearsal of `chip_smoke.py`: its phase functions at reduced
size, kernels in interpret mode, so the script's control flow is
guarded here while `main()` still refuses any device but a TPU."""
import os
import subprocess
import sys

import pytest

from repro.configs import ARCHS, reduced_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_a_cpu(argv, capsys):
    assert chip_smoke.main(argv) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out and "needs" in out.err


def test_one_chip_phases_reduced(tmp_path):
    cfg = reduced_config(ARCHS["qwen2-0.5b"])
    rc = chip_smoke.run_config(cfg, batch=2, seq=64)
    a = chip_smoke.train_and_save(cfg, rc, str(tmp_path), use_pallas=True,
                                  delta_params=True)
    assert a["saved_step"] == 4 and len(a["stalls"]) == 2
    assert a["delta_arrays"][2] == 0 < a["delta_arrays"][4]
    # four timed steps: 1, 3 (which may begin while the first save is
    # still being written), 5 and 6
    st = a["step_times"]
    assert len(st["writer"]) <= 1
    assert sum(map(len, st.values())) == 4 and st["clean"]
    k = chip_smoke.kernel_check(a["host"]["opt"]["v"]["embed"]["embedding"],
                                on_tpu=False)
    assert all(k["same"].values())
    b = chip_smoke.restore_and_resume(cfg, rc, str(tmp_path), a,
                                      use_pallas=True)
    chip_smoke.report_train(cfg, rc, a)
    chip_smoke.report_restore(a, b, exact_losses=True)
    assert b["losses"] == a["ref_losses"]


def test_state_mismatches_sees_one_flipped_bit():
    import jax.numpy as jnp
    import numpy as np
    host = {"a": np.zeros(3, np.float32), "b": {"c": np.ones(2, np.int32)}}
    state = {"a": jnp.zeros(3), "b": {"c": jnp.ones(2, jnp.int32)}}
    assert chip_smoke.state_mismatches(state, host) == []
    host["a"].view(np.uint32)[1] ^= 1
    assert chip_smoke.state_mismatches(state, host) == ["['a']"]


FOUR = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import chip_smoke
from repro.configs import ARCHS, reduced_config
from repro.launch.mesh import make_mesh
cfg = reduced_config(ARCHS["qwen2-1.5b"], pad_to=4)
rc = chip_smoke.run_config(cfg, batch=4, seq=64)
a = chip_smoke.train_and_save(cfg, rc, sys.argv[2], saves=1,
                              mesh=make_mesh((2, 2), ("data", "model")))
b = chip_smoke.restore_and_resume(cfg, rc, sys.argv[2], a,
                                  mesh=make_mesh((1, 4), ("data", "model")))
chip_smoke.report_train(cfg, rc, a)
chip_smoke.report_restore(a, b, exact_losses=False)
chip_smoke.check_spread(a, b)
print("FOUR_OK")
"""


def test_four_chip_phases_reduced_on_virtual_devices(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", FOUR, REPO, str(tmp_path)],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FOUR_OK" in out.stdout


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_compile_cache_location(env_dir, tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set; without
    it the cache sits at the checkout's fixed `.jax_cache`."""
    import jax

    from repro.launch.compile_cache import ENV, enable_compile_cache
    if env_dir is None:
        monkeypatch.delenv(ENV, raising=False)
    else:
        monkeypatch.setenv(ENV, str(tmp_path / env_dir))
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    try:
        path = enable_compile_cache(REPO)
        now = jax.config.jax_compilation_cache_dir
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
    if env_dir is None:
        assert path == now == os.path.join(REPO, ".jax_cache")
    else:
        assert path == str(tmp_path / env_dir)
        assert now == before["jax_compilation_cache_dir"]


def test_compile_cache_needs_a_checkout(monkeypatch):
    """The package finds the checkout it runs from; an installed copy
    (no checkout) caches only where JAX_COMPILATION_CACHE_DIR says."""
    import jax

    from repro.launch.compile_cache import (ENV, checkout_root,
                                            enable_compile_cache)
    assert checkout_root() == REPO
    monkeypatch.delenv(ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache(None) is None
    assert jax.config.jax_compilation_cache_dir == before
