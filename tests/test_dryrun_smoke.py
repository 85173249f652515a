"""Dry-run machinery smoke: one real cell on the 512-device production
mesh (subprocess; the full 40-cell x 2-mesh sweep is run by
`python -m repro.launch.dryrun --all --mesh both` and recorded in
EXPERIMENTS.md §Dry-run)."""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import json
from repro.launch.dryrun import run_cell
cell = run_cell("qwen1.5-0.5b", "decode_32k", multi_pod=True)
cell.pop("trace", None)
print(json.dumps(cell))
"""


@pytest.mark.slow
def test_one_cell_on_multipod_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    cell = json.loads(out.stdout.strip().splitlines()[-1])
    assert cell["status"] == "ok", cell
    assert cell["mesh"] == "2x16x16"
    assert cell["hlo"]["dot_flops"] > 0
    assert cell["memory"]["peak_bytes"] is not None


def test_hlo_analyzer_trip_counts():
    """The roofline analyzer must expand while-loop trip counts
    (cost_analysis does not — the finding is documented in §Roofline)."""
    import jax
    import jax.numpy as jnp
    from repro.launch.hlo_analysis import analyze_hlo

    def f(x, w):
        def body(c, wl):
            return jnp.tanh(c @ wl), None
        return jax.lax.scan(body, x, w)[0]

    c = jax.jit(f).lower(
        jax.ShapeDtypeStruct((8, 16), jnp.float32),
        jax.ShapeDtypeStruct((12, 16, 16), jnp.float32)).compile()
    r = analyze_hlo(c.as_text())
    assert r["dot_flops"] == 12 * 2 * 8 * 16 * 16
    raw = c.cost_analysis()["flops"]
    assert raw < r["dot_flops"]  # the undercount being corrected
