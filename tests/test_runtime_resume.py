"""MANARuntime end-to-end: bit-identical resume, preemption triggers,
checkpoint cadence, data-pipeline determinism."""
import jax
import numpy as np
import pytest

from repro.configs import ARCHS, reduced_config
from repro.configs.base import RunConfig, ShapeConfig
from repro.core.runtime import MANARuntime
from repro.data.pipeline import SyntheticDataset

SHAPE = ShapeConfig("smoke", 64, 2, "train")


def _rc(cfg):
    return RunConfig(model=cfg, shape=SHAPE, loss_chunk=32, attn_chunk=16)


def test_bitwise_resume(tmp_path):
    cfg = reduced_config(ARCHS["qwen2-0.5b"])
    rc = _rc(cfg)
    rt = MANARuntime(cfg, rc, ckpt_dir=str(tmp_path), ckpt_every_steps=4)
    rt.initialize()
    hist = rt.run(10)
    assert rt.checkpoints_taken == 2
    assert rt.ckpt.steps() == [4, 8]

    rt2 = MANARuntime(cfg, rc, ckpt_dir=str(tmp_path))
    start = rt2.restore(8)
    assert start == 8
    hist2 = rt2.run(2)
    a = [h["loss"] for h in hist][8:10]
    b = [h["loss"] for h in hist2]
    assert a == b, "resume must be bit-identical (same batches, same state)"


def test_async_pipeline_resume(tmp_path):
    """ISSUE 4: the async 2PC split through the real runtime — the safe
    point stages and returns, the background writer + writer-ack
    finalize the epoch, and the written image restores bit-identically
    into a SYNC runtime (the file format is mode-agnostic)."""
    cfg = reduced_config(ARCHS["qwen2-0.5b"])
    rc = _rc(cfg)
    rt = MANARuntime(cfg, rc, ckpt_dir=str(tmp_path), ckpt_every_steps=4,
                     async_ckpt=True)
    rt.initialize()
    hist = rt.run(6)
    assert rt.checkpoints_taken == 1
    assert rt.ckpt.steps() == [4]
    assert rt.agent.stats["async_stages"] == 1

    rt2 = MANARuntime(cfg, rc, ckpt_dir=str(tmp_path))
    assert rt2.restore(4) == 4
    hist2 = rt2.run(2)
    assert [h["loss"] for h in hist][4:6] == [h["loss"] for h in hist2]


def _donating(monkeypatch):
    """`LowerHalf.build` as on an accelerator, where the step takes its
    input state's buffers for its output (on the CPU it does not)."""
    from repro.core.split_state import LowerHalf
    build = LowerHalf.build.__func__

    def on_chip(cls, *a, **kw):
        with monkeypatch.context() as m:
            m.setattr(jax, "default_backend", lambda: "tpu")
            return build(cls, *a, **kw)

    monkeypatch.setattr(LowerHalf, "build", classmethod(on_chip))


@pytest.mark.parametrize("arch,async_ckpt", [("qwen2-0.5b", False),
                                             ("qwen2-0.5b", True),
                                             ("moonlight-16b-a3b", False)])
def test_resume_with_the_state_donated(tmp_path, monkeypatch, arch,
                                       async_ckpt):
    """A donated state is gone after its step, so the safe point's
    snapshot must be a finished host copy: the image restores and the
    run resumes bit-identically."""
    _donating(monkeypatch)
    cfg = reduced_config(ARCHS[arch])
    rc = _rc(cfg)
    rt = MANARuntime(cfg, rc, ckpt_dir=str(tmp_path), ckpt_every_steps=4,
                     async_ckpt=async_ckpt)
    rt.initialize()
    first = rt.state
    hist = rt.run(6)
    assert all(x.is_deleted() for x in jax.tree.leaves(first))
    rt2 = MANARuntime(cfg, rc, ckpt_dir=str(tmp_path))
    assert rt2.restore(4) == 4
    assert [h["loss"] for h in rt2.run(2)] == [h["loss"] for h in hist][4:6]


def test_resume_wrong_arch_rejected(tmp_path):
    cfg = reduced_config(ARCHS["qwen2-0.5b"])
    rt = MANARuntime(cfg, _rc(cfg), ckpt_dir=str(tmp_path),
                     ckpt_every_steps=2)
    rt.initialize()
    rt.run(3)
    cfg2 = reduced_config(ARCHS["rwkv6-3b"])
    rt2 = MANARuntime(cfg2, _rc(cfg2), ckpt_dir=str(tmp_path))
    with pytest.raises(ValueError, match="arch"):
        rt2.restore()


def test_explicit_preemption_request(tmp_path):
    """The operational trigger: an external checkpoint request lands at
    the next safe point (paper §I: preemption / end-of-allocation)."""
    cfg = reduced_config(ARCHS["qwen1.5-0.5b"])
    rt = MANARuntime(cfg, _rc(cfg), ckpt_dir=str(tmp_path))
    rt.initialize()
    rt.run(2)
    assert rt.checkpoints_taken == 0
    rt.request_checkpoint()
    rt.run(1)
    assert rt.checkpoints_taken == 1
    assert rt.ckpt.latest_step() == 3


def test_dataset_determinism_and_cursor():
    cfg = reduced_config(ARCHS["qwen2-0.5b"])
    ds = SyntheticDataset(cfg, SHAPE, seed=5)
    a = ds.get_batch(17)
    b = SyntheticDataset.from_state(cfg, SHAPE, ds.state_dict(17)).get_batch(17)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = ds.get_batch(18)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_agent_tables_serialized_into_checkpoint(tmp_path):
    cfg = reduced_config(ARCHS["qwen2-0.5b"])
    rt = MANARuntime(cfg, _rc(cfg), ckpt_dir=str(tmp_path),
                     ckpt_every_steps=2)
    rt.initialize()
    rt.run(3)
    _, extra = rt.ckpt.restore()
    assert "agent" in extra
    assert "comms" in extra["agent"]
    # world comm membership survives as upper-half state
    comms = extra["agent"]["comms"]["comms"]
    assert list(comms.values())[0] == [0]
