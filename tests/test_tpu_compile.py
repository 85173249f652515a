"""Ahead-of-time compiles of the chip path for one described TPU v5e.

Nothing runs: the TPU compiler, installed beside the CPU backend,
compiles for a v5e chip that is described and not attached.  That
refuses what interpret mode accepts (block shapes off the (8, 128)
tiling, unsupported ops) and a program that does not fit HBM.  The
topology is described inside a fixture, never at import, and the file
skips where it cannot be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS
from repro.configs.base import RunConfig, ShapeConfig
from repro.kernels.checksum import ops as cops
from repro.kernels.checksum.ref import BLOCK
from repro.kernels.delta import ops as dops
from repro.kernels.quantize import ops as qops
from repro.training.step import abstract_train_state, make_train_step

V5E_HBM_BYTES = 15.75 * 2**30      # what XLA:TPU reports as usable
CHUNK_WORDS = (64 << 20) // (4 * BLOCK)           # one 64 MiB chunk
EMBED_WORDS = 151936 * 896 * 4 // (4 * BLOCK)     # qwen2-0.5b embedding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("rows", [CHUNK_WORDS, EMBED_WORDS, 1])
def test_checksum_kernel_compiles(one_chip, rows):
    c = _compile(lambda w: cops.checksum_words(w, interpret=False), one_chip,
                 ((rows, BLOCK), jnp.uint32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("rows", [CHUNK_WORDS, EMBED_WORDS, 3])
def test_delta_kernel_compiles(one_chip, rows):
    c = _compile(lambda a, b: dops.delta_words(a, b, interpret=False),
                 one_chip, ((rows, BLOCK), jnp.uint32),
                 ((rows, BLOCK), jnp.uint32))
    assert "tpu_custom_call" in c.as_text()
    # streamed tile by tile: no device temporaries beyond the output
    assert c.memory_analysis().temp_size_in_bytes == 0


# 13 blocks (a row count off the 8-row tiling), and
# one full-width Adam moment leaf
@pytest.mark.parametrize("n", [12 * 1024 + 5, 151936 * 896])
def test_quantize_kernel_compiles(one_chip, n):
    c = _compile(lambda x: qops.quantize(x, interpret=False), one_chip,
                 ((n,), jnp.float32))
    assert "tpu_custom_call" in c.as_text()


def test_dequantize_kernel_compiles(one_chip):
    c = _compile(lambda q, s: qops.dequantize(q, s, interpret=False),
                 one_chip, ((12, 1024), jnp.int8), ((12, 1), jnp.float32))
    assert "tpu_custom_call" in c.as_text()


def test_full_width_train_step_fits_one_chip(one_chip):
    """qwen2-0.5b at published widths, f32 params + AdamW, bf16 compute,
    batch 8 x seq 2048: the shape `chip_smoke.py` trains."""
    cfg = ARCHS["qwen2-0.5b"]
    rc = RunConfig(model=cfg, shape=ShapeConfig("chip_smoke", 2048, 8,
                                                "train"),
                   loss_chunk=512, attn_chunk=512)
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        abstract_train_state(cfg, rc))
    batch = {k: jax.ShapeDtypeStruct((8, 2048), jnp.int32, sharding=one_chip)
             for k in ("tokens", "labels")}
    m = jax.jit(make_train_step(cfg, rc, None)).lower(
        state, batch).compile().memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, used / 2**30


def test_expert_share_train_step_fits_one_chip(one_chip):
    """moonlight-16b-a3b as the benchmark cell holds it: 1 dense + 4
    expert layers, 8 of 64 experts, a 20,480-row vocabulary slice, two
    8,192-token sequences; the state is donated (as the runtime's jit
    takes it on the chip), so it is held once beside the gradient and
    the temporaries."""
    import dataclasses
    base = ARCHS["moonlight-16b-a3b"]
    cfg = dataclasses.replace(base, n_layers=5, vocab_size=20480, pad_to=1,
                              moe=dataclasses.replace(base.moe, held=8))
    rc = RunConfig(model=cfg, shape=ShapeConfig("save_sparse_8k", 8192, 2,
                                                "train"),
                   loss_chunk=512, attn_chunk=512)
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        abstract_train_state(cfg, rc))
    batch = {k: jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=one_chip)
             for k in ("tokens", "labels")}
    c = jax.jit(make_train_step(cfg, rc, None), donate_argnums=0).lower(
        state, batch).compile()
    m = c.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert m.alias_size_in_bytes >= m.output_size_in_bytes - 2 ** 20
    assert used < V5E_HBM_BYTES, used / 2**30
    assert "ragged-dot" in c.as_text()     # the grouped matmuls on the MXU
