"""The DeepSeek-V3 block (Moonlight-16B-A3B) at a tiny size on the CPU:
the program against the plain reference (`benchmarks/chip/references/
deepseek_v3.py`), the reference against transformers' modeling code,
the expert share, dropless routing, a checkpointed resume, and the
guard that the dense path lowers as before."""
import dataclasses
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced_config
from repro.configs.base import ModelConfig, MoEConfig, RunConfig, ShapeConfig
from repro.models import moe as moe_mod
from repro.models import transformer as T
from repro.models.layers import mlp_apply

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP = os.path.join(REPO, "benchmarks", "chip")
sys.path.insert(0, CHIP)
import harness  # noqa: E402

REF = harness.load_module(os.path.join(CHIP, "references", "deepseek_v3.py"),
                          "ref_deepseek_v3_tier1")

# a tiny configuration in the benchmark file's keys: 1 dense + 2 expert
# layers; the router 8 wide, top-3, this chip holding experts 2-5
TINY = {
    "name": "moonlight-tiny", "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_attention_heads": 4,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "n_routed_experts": 4, "n_shared_experts": 2, "num_experts_per_tok": 3,
    "vocab_size": 256, "rope_theta": 50000, "rms_norm_eps": 1e-5,
    "routed_scaling_factor": 2.446, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "initializer_range": 0.1, "balance_loss_alpha": 0.01,
    "layout": {"router_experts": 8, "first_held_expert": 2},
}


def _batch(cfg, seed, B=2, S=32):
    rng = np.random.RandomState(seed)
    seq = rng.randint(0, cfg["vocab_size"], (B, S + 1))
    return jnp.asarray(seq[:, :-1], jnp.int32), jnp.asarray(seq[:, 1:],
                                                            jnp.int32)


def _program(cfg, S=32, dtype="float32"):
    model = ModelConfig(arch_id=cfg["name"], **REF.program_model(cfg))
    rc = RunConfig(model=model, shape=ShapeConfig("t", S, 2, "train"),
                   loss_chunk=16, attn_chunk=8, dtype=dtype)
    return model, rc


@pytest.mark.parametrize("seed", [0, 1])
def test_program_matches_the_reference(seed):
    """Loss (with the balance loss) and every gradient leaf, the program
    computing in float32, on the reference's seeded weights."""
    model, rc = _program(TINY)
    params = REF.init_params(TINY, seed)
    tokens, labels = _batch(TINY, seed)

    def prog(p):
        return T.forward_loss(p, model, rc, None,
                              {"tokens": tokens, "labels": labels})[0]

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(prog)(params)
        lr, gr = jax.value_and_grad(REF.loss)(params, tokens, labels, TINY)
    assert abs(float(lp) - float(lr)) < 1e-5 * abs(float(lr))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(gp),
                            jax.tree.leaves(gr)):
        a, b = np.asarray(a), np.asarray(b)
        scale = np.abs(b).max()
        assert scale > 0, jax.tree_util.keystr(path)
        assert np.abs(a - b).max() <= 1e-4 * scale, jax.tree_util.keystr(path)


def test_reference_logits_match_transformers():
    """The reference's logits against transformers' DeepseekV3ForCausalLM
    with the same weights, every expert held, to float32 rounding."""
    pytest.importorskip("torch")
    pytest.importorskip("transformers")
    import torch
    from transformers import DeepseekV3Config, DeepseekV3ForCausalLM

    cfg = dict(TINY, n_routed_experts=8,
               layout={"router_experts": 8, "first_held_expert": 0})
    hf = DeepseekV3Config(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4, n_shared_experts=2,
        n_routed_experts=8, routed_scaling_factor=2.446, kv_lora_rank=32,
        q_lora_rank=None, qk_rope_head_dim=8, v_head_dim=16,
        qk_nope_head_dim=16, n_group=1, topk_group=1, num_experts_per_tok=3,
        first_k_dense_replace=1, norm_topk_prob=True, rope_theta=50000,
        rms_norm_eps=1e-5, rope_interleave=True, tie_word_embeddings=False,
        attention_bias=False)
    hf._attn_implementation = "eager"
    model = DeepseekV3ForCausalLM(hf).eval()
    params = jax.tree.map(np.asarray, REF.init_params(cfg, 3))

    def t(a):
        return torch.tensor(np.ascontiguousarray(a), dtype=torch.float32)

    sd = {"model.embed_tokens.weight": t(params["embed"]["embedding"]),
          "lm_head.weight": t(params["embed"]["head"].T),
          "model.norm.weight": t(params["ln_f"])}
    stacks = [("dense_blocks", i, i) for i in range(1)] + \
        [("blocks", i, i + 1) for i in range(2)]
    for stack, i, layer in stacks:
        p = jax.tree.map(lambda a: a[i], params[stack])
        a = p["attn"]
        pre = f"model.layers.{layer}."
        sd.update({
            pre + "input_layernorm.weight": t(p["ln1"]),
            pre + "post_attention_layernorm.weight": t(p["ln2"]),
            pre + "self_attn.q_proj.weight": t(a["wq"].reshape(64, -1).T),
            pre + "self_attn.kv_a_proj_with_mqa.weight": t(a["wkv_a"].T),
            pre + "self_attn.kv_a_layernorm.weight": t(a["kv_norm"]),
            pre + "self_attn.kv_b_proj.weight": t(a["wkv_b"].reshape(32, -1).T),
            pre + "self_attn.o_proj.weight": t(a["wo"].reshape(-1, 64).T)})
        if stack == "dense_blocks":
            mlps = {"mlp.": p["mlp"]}
        else:
            m = p["moe"]
            sd[pre + "mlp.gate.weight"] = t(m["router"].T)
            mlps = {f"mlp.experts.{j}.": {"wg": m["wg"][j], "wi": m["wi"][j],
                                          "wo": m["wo"][j]} for j in range(8)}
            mlps["mlp.shared_experts."] = m["shared"]
        for name, w in mlps.items():
            sd[pre + name + "gate_proj.weight"] = t(w["wg"].T)
            sd[pre + name + "up_proj.weight"] = t(w["wi"].T)
            sd[pre + name + "down_proj.weight"] = t(w["wo"].T)
    missing, unexpected = model.load_state_dict(sd, strict=False)
    assert not unexpected and all("e_score_correction_bias" in k
                                  for k in missing), (missing, unexpected)
    tokens, _ = _batch(cfg, 4)
    with torch.no_grad():
        want = model(torch.tensor(np.asarray(tokens))).logits.numpy()
    with jax.default_matmul_precision("highest"):
        got = np.asarray(REF.logits(params, tokens, cfg))
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())


def _layer(num_experts=16, top_k=4, seed=0, N=(2, 16), d=64):
    moe = MoEConfig(num_experts=num_experts, top_k=top_k, router="deepseek_v3",
                    d_expert=32, n_shared=2, routed_scale=2.446)
    p, _ = moe_mod.init_dropless_moe(jax.random.PRNGKey(seed), d, 32,
                                     num_experts, num_experts, 2)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), N + (d,))
    return moe, p, x


@pytest.mark.parametrize("shares", [2, 8])
def test_the_expert_shares_add_up_to_the_whole_layer(shares):
    """Each share routes over all experts and computes its own experts'
    part; the shares' routed parts, with the shared experts counted
    once, add up to the uncut layer."""
    moe, p, x = _layer()
    whole, _ = moe_mod.dropless_moe_apply(p, x, moe)
    held = moe.num_experts // shares
    total = mlp_apply(p["shared"], x)
    assignments = 0.0
    for s in range(shares):
        part = dict(p, **{k: p[k][s * held:(s + 1) * held]
                          for k in ("wi", "wg", "wo")})
        del part["shared"]
        y, stats = moe_mod.dropless_moe_apply(part, x, dataclasses.replace(
            moe, held=held, first_held=s * held, n_shared=0))
        total = total + y
        assignments += float(stats["tokens_held"])
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    assert assignments == x.shape[0] * x.shape[1] * moe.top_k


def _dense_per_expert(p, x, moe):
    """Per token: sigmoid scores, top-k, renormalised and scaled gates,
    each chosen expert's SwiGLU, plus the shared experts (numpy)."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    xs = np.asarray(x, np.float64).reshape(-1, x.shape[-1])

    def swiglu(t, wg, wi, wo):
        h = t @ wg
        return ((h / (1 + np.exp(-h))) * (t @ wi)) @ wo

    out = []
    for t in xs:
        s = 1 / (1 + np.exp(-(t @ p["router"])))
        top = np.argsort(-s, kind="stable")[:moe.top_k]
        g = s[top] / s[top].sum() * moe.routed_scale
        y = sum(gj * swiglu(t, p["wg"][j], p["wi"][j], p["wo"][j])
                for gj, j in zip(g, top))
        sh = p["shared"]
        out.append(y + swiglu(t, sh["wg"], sh["wi"], sh["wo"]))
    return np.stack(out).reshape(x.shape)


def test_dropless_under_a_skewed_router():
    """Every token's top choice is expert 0: all of them are computed
    there (a capacity of 1.25x the mean would drop most), and the layer
    equals the dense per-expert computation."""
    moe, p, x = _layer(num_experts=4, top_k=2)
    x = jnp.abs(x) + 0.5
    p = dict(p, router=p["router"].at[:, 0].set(1.0))
    y, stats = moe_mod.dropless_moe_apply(p, x, moe)
    n = x.shape[0] * x.shape[1]
    router = np.asarray(x).reshape(n, -1) @ np.asarray(p["router"])
    assert (router.argmax(-1) == 0).all()
    assert float(stats["tokens_held"]) == n * moe.top_k
    # expert 0 takes all n tokens: 2x the mean load of n * k / E
    assert float(stats["max_load"]) == pytest.approx(moe.num_experts
                                                     / moe.top_k)
    np.testing.assert_allclose(np.asarray(y), _dense_per_expert(p, x, moe),
                               rtol=1e-4, atol=1e-4)


def test_a_moe_state_resumes_bit_exact(tmp_path):
    """`MANARuntime` saves the expert stacks as a full image, then an XOR
    delta (digests in interpret mode); the restored state is bit-
    identical and the resumed losses equal the uninterrupted run's."""
    sys.path.insert(0, REPO)
    import chip_smoke
    cfg = reduced_config(ARCHS["moonlight-16b-a3b"])
    rc = chip_smoke.run_config(cfg, batch=2, seq=64)
    a = chip_smoke.train_and_save(cfg, rc, str(tmp_path), use_pallas=True,
                                  delta_params=True)
    assert a["saved_step"] == 4
    assert a["delta_arrays"][2] == 0 < a["delta_arrays"][4]
    assert a["host"]["params"]["blocks"]["moe"]["wi"].ndim == 4
    b = chip_smoke.restore_and_resume(cfg, rc, str(tmp_path), a,
                                      use_pallas=True)
    assert b["start"] == 4 and b["mismatched"] == []
    assert b["losses"] == a["ref_losses"]


def test_a_mesh_gives_every_new_leaf_a_spec():
    """The expert stacks and MLA's latent leaves map to logical axes the
    rules know: the held experts and the latent are not split again,
    heads go over `model`, the moments over `data` (ZeRO-1)."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.sharding.rules import ShardingRules
    from repro.training.step import train_state_specs
    cfg = reduced_config(ARCHS["moonlight-16b-a3b"])
    rc = RunConfig(model=cfg, shape=ShapeConfig("t", 64, 2, "train"))
    specs = train_state_specs(cfg, rc, ShardingRules(
        make_mesh((1, 1), ("data", "model"))))
    blocks = specs["params"]["blocks"]
    assert blocks["moe"]["wi"] == P(None, None, None, None)
    assert blocks["attn"]["wkv_a"] == P(None, None, None)
    assert blocks["attn"]["wq"] == P(None, None, "model", None)
    assert specs["opt"]["m"]["blocks"]["moe"]["wi"] == P("data", None, None,
                                                         None)


# sha256 of the lowered text of reduced qwen2-0.5b's train step (batch
# 2 x 64, loss_chunk 32, attn_chunk 32, on the CPU), taken from the
# tree before the DeepSeek-V3 block was added
QWEN2_STEP_DIGEST = (
    "664e77b59ae7b29d3203fb16f098e98aee2964f935e19d7ceedcc75091b481d3")


def test_the_dense_train_step_lowers_as_before():
    from repro.training.step import abstract_train_state, make_train_step
    cfg = reduced_config(ARCHS["qwen2-0.5b"])
    rc = RunConfig(model=cfg, shape=ShapeConfig("guard", 64, 2, "train"),
                   loss_chunk=32, attn_chunk=32)
    batch = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32)
             for k in ("tokens", "labels")}
    text = jax.jit(make_train_step(cfg, rc, None)).lower(
        abstract_train_state(cfg, rc), batch).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == QWEN2_STEP_DIGEST
