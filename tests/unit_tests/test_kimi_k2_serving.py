"""Kimi-K2 (``model_type: kimi_k2``, the DeepSeek-V3 classes) through the
serving path: the latent (MLA) paged cache and its two rungs, the absorbed
form against the expanded one, the held-experts share, the engine against
``generate()`` and the registry (the compiled step's instruction list is
``test_program_spans.py``'s).  Small sizes,
seeded random weights, float32; the plain reference is the benchmark's
(``benchmark/reference/kimi_k2.py``), which imports nothing of the
program."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.generation import GenerationConfig, generate
from automodel_tpu.models.auto_model import build_model
from automodel_tpu.models.deepseek_v3 import (
    DeepseekV3Config,
    DeepseekV3ForCausalLM,
)
from automodel_tpu.ops import mla_paged_attention_kernel as mla_kernel
from automodel_tpu.ops.kernel_lib import parity
from automodel_tpu.serving import DecodeEngine, ServingConfig
from automodel_tpu.serving.kv_cache import (
    PagedKVView,
    init_paged_pools,
    latent_plane_width,
)
from benchmark import weights as bench_weights
from benchmark.reference import kimi_k2 as ref

# The benchmark's toy configuration: 1 dense + 2 expert layers, a 16-wide
# router of which experts 4..7 are held, YaRN, interleaved rope.
CFG = {
    "model_type": "kimi_k2", "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 128, "qk_nope_head_dim": 16, "qk_rope_head_dim": 16,
    "v_head_dim": 16, "n_routed_experts": 4, "num_experts_per_tok": 4,
    "n_shared_experts": 1, "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "first_k_dense_replace": 1, "vocab_size": 512,
    "max_position_embeddings": 4096, "rope_theta": 10000.0,
    "rope_scaling": {"type": "yarn", "factor": 8,
                     "original_max_position_embeddings": 64, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1},
    "rms_norm_eps": 1e-05, "tie_word_embeddings": False,
    "attention_bias": False, "torch_dtype": "bfloat16",
    "published": {"n_routed_experts": 16},
    "deployment": {"held_experts_first": 4},
}


@pytest.fixture(scope="module")
def world():
    model = build_model(config=ref.model_config(CFG),
                        compute_dtype=jnp.float32, remat=False)
    flat = jax.jit(lambda w: ref.make(CFG, w))(
        bench_weights.seed_words(2 ** 31 + 77))
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          ref.to_program_tree(flat))
    return model, flat, params


def _reference_logits(flat, ids):
    h = ref.hidden_states(flat, CFG, jnp.asarray(ids), q_block=32)
    with jax.default_matmul_precision("highest"):
        return np.asarray(
            ref.rms_norm(h, flat["final_norm"].astype(jnp.float32), 1e-5)
            @ flat["lm_head"].astype(jnp.float32))


def _paged_logits(model, params, ids, chunk, block_size=8, decode_from=None):
    """``ids [T]`` through the latent paged cache as the engine steps it:
    chunks of ``chunk`` tokens up to ``decode_from``, then one at a time;
    one row, scrambled block table.  Returns logits ``[T, V]``."""
    T = len(ids)
    decode_from = T if decode_from is None else decode_from
    mb = -(-T // block_size)
    pools = init_paged_pools(
        num_layers=model.config.num_hidden_layers, num_blocks=mb + 1,
        block_size=block_size, cache_dtype=jnp.float32, quantized=False,
        planes=model.paged_cache_planes())
    table = np.random.default_rng(3).permutation(np.arange(1, mb + 1))

    @jax.jit        # one program per width, as the engine has
    def step(pools, toks, pos, slots, ctx):
        view = PagedKVView(pools, jnp.asarray(table[None], jnp.int32), slots,
                           ctx, pos, block_size=block_size)
        return model(params, toks, position_ids=pos, kv_cache=view)

    out, start = [], 0
    while start < T:
        w = chunk if start < decode_from else 1
        n = min(w, (decode_from if start < decode_from else T) - start)
        pos = start + np.minimum(np.arange(w), n - 1)
        toks = np.zeros((w,), np.int32)
        toks[:n] = ids[start:start + n]
        slots = np.arange(w) % block_size           # pads: the null page
        slots[:n] = [table[p // block_size] * block_size + p % block_size
                     for p in range(start, start + n)]
        res = step(pools, jnp.asarray(toks[None]),
                   jnp.asarray(pos[None], jnp.int32),
                   jnp.asarray(slots[None], jnp.int32),
                   jnp.asarray([start + n], jnp.int32))
        pools = res["kv_cache"]
        assert res["expert_tokens"].shape == (2, 4)
        assert int(res["expert_tokens"].sum()) <= 4 * n * 2   # pads unrouted
        out.append(np.asarray(res["logits"][0, :n]))
        start += n
    return np.concatenate(out)


# -- (a) prefill then decode through the latent cache == the reference -------
@pytest.mark.parametrize("interpret", [False, True],
                         ids=["mla_paged_gather", "mla_paged_decode"])
@pytest.mark.parametrize("chunk", [1, 8], ids=["width1", "chunked"])
def test_latent_paged_cache_matches_the_plain_reference(
        world, monkeypatch, interpret, chunk):
    model, flat, params = world
    monkeypatch.setattr(mla_kernel, "_INTERPRET", interpret)
    ids = np.random.default_rng(0).integers(1, 512, 41).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = _paged_logits(model, params, ids, chunk, decode_from=29)
    want = _reference_logits(flat, np.pad(ids, (0, 23)))[:41]
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


_MLA_CASES = parity.mla_paged_attention_cases()


@pytest.mark.parametrize("rung", ["attention.mla_paged_gather",
                                  "attention.mla_paged_decode"])
@pytest.mark.parametrize("case", _MLA_CASES, ids=[c["name"] for c in _MLA_CASES])
def test_mla_rungs_match_their_reference(rung, case):
    parity.run_mla_paged_attention_parity(rung, case)


# -- (b) absorbed form == expanded form --------------------------------------
def test_absorbed_decode_equals_the_expanded_forward(world):
    """The serving path attends ``q_nope W_uk^T`` against the latent; the
    training forward expands k and v per head.  Same mathematics."""
    model, _, params = world
    ids = np.random.default_rng(1).integers(1, 512, 24).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        expanded = np.asarray(model(params, jnp.asarray(ids[None]))["logits"][0])
        absorbed = _paged_logits(model, params, ids, 8, decode_from=16)
    np.testing.assert_allclose(absorbed, expanded, atol=2e-5, rtol=1e-4)


# -- (c) the share test -------------------------------------------------------
@pytest.mark.parametrize("serving", [True, False],
                         ids=["decode_dispatch", "training_dispatch"])
def test_four_shares_add_up_to_the_whole_layer(serving):
    """E = 16 in 4 shares of 4: each share routes over all 16, normalises
    over everything it chose and computes only what it holds; the routed
    parts of the four plus the shared expert ONCE are the uncut reference's
    whole layer."""
    E, k, H, Im, T = 16, 4, 64, 32, 24
    rng = np.random.default_rng(5)
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.2, jnp.float32)
    full = {"router": f32(H, E), "e_gate": f32(E, H, Im), "e_up": f32(E, H, Im),
            "e_down": f32(E, Im, H), "s_gate": f32(H, Im), "s_up": f32(H, Im),
            "s_down": f32(Im, H)}
    x = f32(2, T // 2, H)
    with jax.default_matmul_precision("highest"):
        routed, shared = ref.expert_layer_ffn(full, x.reshape(T, H), k, 2.5, 0)
        whole = np.asarray(routed + shared)
        total = np.zeros((T, H), np.float32)
        for first in range(0, E, 4):
            cfg = DeepseekV3Config(
                vocab_size=32, hidden_size=H, intermediate_size=64,
                num_hidden_layers=2, num_attention_heads=4,
                moe_intermediate_size=Im, n_routed_experts=E,
                num_experts_per_tok=k, routed_scaling_factor=2.5,
                held_experts=[first, 4], moe_capacity_factor=None)
            model = DeepseekV3ForCausalLM(cfg, compute_dtype=jnp.float32,
                                          remat=False)
            held = slice(first, first + 4)
            experts = {n: {"kernel": full["e_" + n.split("_")[0]][held]}
                       for n in ("gate_proj", "up_proj", "down_proj")}
            p = {"gate": {"kernel": full["router"],
                          "e_score_correction_bias": jnp.zeros((E,))},
                 "shared_experts": {
                     n: {"kernel": full["s_" + n.split("_")[0]]}
                     for n in ("gate_proj", "up_proj", "down_proj")}}
            if serving:
                stacks = jax.tree.map(lambda a: a[None], experts)
                out, counts = model._moe_mlp_serving(
                    x, p, jnp.ones(x.shape[:2], bool), stacks, 0)
                assert counts.shape == (4,)
            else:
                out = model._moe_mlp(x, dict(p, experts=experts))
            # a share's own routed part agrees with the reference given
            # the same share
            part, _ = ref.expert_layer_ffn(
                dict(full, **{"e_" + n: full["e_" + n][held]
                              for n in ("gate", "up", "down")}),
                x.reshape(T, H), k, 2.5, first)
            mine = np.asarray(out).reshape(T, H) - np.asarray(shared)
            np.testing.assert_allclose(mine, np.asarray(part), atol=2e-5)
            total += mine
    np.testing.assert_allclose(total + np.asarray(shared), whole, atol=5e-5)


# -- (d) the engine serves it and matches generate() -------------------------
@pytest.mark.parametrize("interpret", [False, True],
                         ids=["mla_paged_gather", "mla_paged_decode"])
def test_engine_serves_kimi_k2_and_matches_generate(world, monkeypatch,
                                                    interpret):
    model, _, params = world
    monkeypatch.setattr(mla_kernel, "_INTERPRET", interpret)
    gen = GenerationConfig(max_new_tokens=10, do_sample=False,
                           eos_token_id=None)
    prompts = np.random.default_rng(2).integers(1, 512, (3, 11))
    want = np.asarray(generate(model, params, jnp.asarray(prompts),
                               config=gen))
    eng = DecodeEngine(
        model, params,
        ServingConfig(kv_block_size=8, max_num_seqs=4, max_model_len=64,
                      prefill_chunk=8), generation=gen)
    assert eng.pools["kv"].shape == (3, 33, 8, latent_plane_width(128 + 16))
    np.testing.assert_array_equal(eng.generate(prompts), want)
    st = eng.stats()
    # two expert layers of four held experts; every step stamps its counts
    assert 0 < st["experts_hit_sum"] <= st["steps"] * 2 * 4
    assert st["experts_hit_sum"] <= st["expert_assignments_sum"]
    assert st["mixed_steps"] and st["decode_steps"]


def test_valid_tokens_leaves_out_pads_and_idle_rows():
    """What the routing counts: a prefilling row's real columns, a decode
    row's one, and nothing of a row no request holds (its table is all null
    page; its position and context read like a first token's)."""
    from automodel_tpu.serving.kv_cache import PagedKVView

    pos = jnp.asarray([[5, 6, 7, 7], [9, 9, 9, 9], [0, 0, 0, 0], [0, 0, 0, 0]],
                      jnp.int32)
    tables = jnp.asarray([[3, 0], [4, 7], [0, 0], [2, 0]], jnp.int32)
    view = PagedKVView({}, tables, jnp.zeros((4, 4), jnp.int32),
                       jnp.asarray([8, 10, 1, 1], jnp.int32), pos,
                       block_size=8)
    np.testing.assert_array_equal(
        np.asarray(view.valid_tokens()),
        [[1, 1, 1, 0], [1, 0, 0, 0], [0, 0, 0, 0], [1, 0, 0, 0]])


def test_a_dense_model_reports_no_expert_counters():
    from automodel_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=64)
    model = LlamaForCausalLM(cfg, param_dtype=jnp.float32,
                             compute_dtype=jnp.float32, remat=False)
    eng = DecodeEngine(model, model.init(jax.random.key(0)),
                       ServingConfig(kv_block_size=8, max_num_seqs=2,
                                     max_model_len=32, prefill_chunk=8),
                       generation=GenerationConfig(max_new_tokens=3))
    eng.generate(np.ones((1, 4), np.int64))
    st = eng.stats()
    assert st["expert_assignments_sum"] is None
    assert st["experts_hit_sum"] is None
    assert sorted(eng.pools) == ["k", "v"]


def test_int8_latent_cache_is_refused_loudly(world):
    model, _, params = world
    with pytest.raises(NotImplementedError, match="latent cache plane"):
        DecodeEngine(model, params, ServingConfig(
            kv_block_size=8, max_num_seqs=2, max_model_len=32,
            kv_cache_dtype="int8"))


# -- the registry -------------------------------------------------------------
# The catalog row of Kimi-K2.6 (``source_url`` https://huggingface.co/
# moonshotai/Kimi-K2.6/blob/main/config.json), its ``config`` verbatim.
KIMI_K2_6 = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "kimi_k2", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 384,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 64,
    "num_nextn_predict_layers": 0, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 50000, "routed_scaling_factor": 2.827,
    "scoring_func": "sigmoid", "seq_aux": True, "tf_legacy_loss": False,
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840,
}


def test_registry_loads_the_published_kimi_k2_config():
    model = build_model(config=dict(KIMI_K2_6))
    assert isinstance(model, DeepseekV3ForCausalLM)
    cfg = model.config
    assert cfg.model_type == "kimi_k2" and cfg.held_experts is None
    shapes = jax.tree.map(lambda a: a.shape, model.abstract_params())
    assert shapes["dense_layers"]["mlp"]["gate_proj"]["kernel"] == (
        1, 7168, 18432)
    moe = shapes["layers"]["mlp"]
    assert moe["gate"]["kernel"] == (60, 7168, 384)
    assert moe["experts"]["down_proj"]["kernel"] == (60, 384, 2048, 7168)
    attn = shapes["layers"]["self_attn"]
    assert attn["kv_a_proj_with_mqa"]["kernel"] == (60, 7168, 576)
    assert attn["kv_b_proj"]["kernel"] == (60, 512, 64 * 256)
    assert model.paged_cache_planes() == {"kv": (576,)}
    n = sum(int(np.prod(a.shape))
            for a in jax.tree.leaves(model.abstract_params()))
    assert 1.02e12 < n < 1.04e12            # the published 1.04T-A32B
    # the softmax scale carries yarn's m^2 (mscale_all_dim = 1, factor 64)
    m = 0.1 * np.log(64) + 1
    assert model._attn_scale == pytest.approx(192 ** -0.5 * m * m)


def test_held_experts_must_lie_within_the_router():
    base = dataclasses.asdict(DeepseekV3Config(n_routed_experts=16))
    with pytest.raises(ValueError, match="held_experts"):
        DeepseekV3Config(**dict(base, held_experts=[14, 4]))
    assert DeepseekV3Config(**dict(base, held_experts=[12, 4])
                            ).n_held_experts == 4
