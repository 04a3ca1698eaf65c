"""Generation: kv-cache decode consistency + HF greedy parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.generation import GenerationConfig, generate
from automodel_tpu.models.llama import LlamaConfig, LlamaForCausalLM

CFG = LlamaConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    rope_theta=10000.0, tie_word_embeddings=True, max_position_embeddings=128)


@pytest.fixture(scope="module")
def model_and_params():
    model = LlamaForCausalLM(CFG, param_dtype=jnp.float32,
                             compute_dtype=jnp.float32, remat=False)
    params = model.init(jax.random.key(0))
    # perturb so argmax isn't degenerate
    leaves, td = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(5), len(leaves))
    params = jax.tree.unflatten(td, [
        l + 0.05 * jax.random.normal(k, l.shape, l.dtype)
        for l, k in zip(leaves, keys)])
    return model, params


def test_cached_decode_matches_full_forward(model_and_params):
    """Prefill + per-token decode must reproduce the full-sequence logits."""
    model, params = model_and_params
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 255, (2, 12)), jnp.int32)

    full = model(params, ids)["logits"]

    from automodel_tpu.generation import DenseKVView

    cache = model.init_kv_cache(2, 12)
    out = model(params, ids[:, :4], kv_cache=DenseKVView.at(cache, 0, 4))
    cache = out["kv_cache"]
    np.testing.assert_allclose(np.asarray(out["logits"]),
                               np.asarray(full[:, :4]), atol=1e-4, rtol=1e-4)
    for t in range(4, 12):
        out = model(params, ids[:, t:t + 1],
                    kv_cache=DenseKVView.at(cache, t, 1))
        cache = out["kv_cache"]
        np.testing.assert_allclose(
            np.asarray(out["logits"][:, 0]), np.asarray(full[:, t]),
            atol=1e-4, rtol=1e-4)


def test_generate_greedy_matches_hf(model_and_params, tmp_path):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    from automodel_tpu.models.hf_io import save_hf_weights

    model, params = model_and_params
    save_hf_weights(model, params, str(tmp_path))
    hf = transformers.AutoModelForCausalLM.from_pretrained(
        str(tmp_path), torch_dtype=torch.float32, attn_implementation="eager")
    hf.eval()

    rng = np.random.default_rng(1)
    # two rows with different prompt lengths exercise the left-pad path
    lens = [9, 6]
    S = max(lens)
    prompts = np.zeros((2, S), np.int64)
    for b, n in enumerate(lens):
        prompts[b, :n] = rng.integers(1, 255, n)

    ours = generate(model, params, prompts, prompt_lens=np.asarray(lens),
                    config=GenerationConfig(max_new_tokens=8))

    for b, n in enumerate(lens):
        row = torch.from_numpy(prompts[b:b + 1, :n])
        with torch.no_grad():
            hf_out = hf.generate(row, max_new_tokens=8, do_sample=False,
                                 pad_token_id=0)
        np.testing.assert_array_equal(ours[b], hf_out[0, n:].numpy())


def test_generate_stops_at_eos(model_and_params):
    model, params = model_and_params
    ids = np.asarray([[5, 6, 7, 8]], np.int32)
    # force eos: pick whatever greedy emits first as the eos id
    first = generate(model, params, ids,
                     config=GenerationConfig(max_new_tokens=1))[0, 0]
    out = generate(model, params, ids,
                   config=GenerationConfig(max_new_tokens=6,
                                           eos_token_id=int(first),
                                           pad_token_id=0))
    assert out[0, 0] == first
    assert all(t == 0 for t in out[0, 1:])


def test_sampling_shapes_and_determinism(model_and_params):
    model, params = model_and_params
    ids = np.asarray([[5, 6, 7, 8]], np.int32)
    cfg = GenerationConfig(max_new_tokens=5, do_sample=True,
                           temperature=0.8, top_k=20, top_p=0.9)
    a = generate(model, params, ids, config=cfg, key=jax.random.key(3))
    b = generate(model, params, ids, config=cfg, key=jax.random.key(3))
    c = generate(model, params, ids, config=cfg, key=jax.random.key(4))
    np.testing.assert_array_equal(a, b)
    assert a.shape == (1, 5) and c.shape == (1, 5)


def test_vlm_generate_with_images():
    from automodel_tpu.models.vision import VisionConfig
    from automodel_tpu.models.vlm import VLMConfig, VLMForConditionalGeneration

    vcfg = VisionConfig(hidden_size=32, intermediate_size=64,
                        num_hidden_layers=1, num_attention_heads=2,
                        image_size=16, patch_size=8)
    cfg = VLMConfig(text_config=CFG, vision_config=vcfg, image_token_id=250)
    model = VLMForConditionalGeneration(cfg, param_dtype=jnp.float32,
                                        compute_dtype=jnp.float32,
                                        remat=False)
    params = model.init(jax.random.key(0))

    n_patches = (16 // 8) ** 2
    prompt = np.concatenate([
        np.full((n_patches,), 250), np.asarray([5, 6, 7])]).astype(np.int32)
    pixels = np.random.default_rng(0).normal(
        size=(1, 16, 16, 3)).astype(np.float32)

    out = generate(model, params, prompt[None, :],
                   config=GenerationConfig(max_new_tokens=4),
                   pixel_values=jnp.asarray(pixels))
    assert out.shape == (1, 4)
    assert (out >= 0).all()

    # the image content must reach the decoder: prefill logits move when
    # the pixels change (deterministic, unlike comparing sampled tokens)
    l1 = model(params, jnp.asarray(prompt[None, :]),
               pixel_values=jnp.asarray(pixels))["logits"]
    l2 = model(params, jnp.asarray(prompt[None, :]),
               pixel_values=jnp.asarray(-pixels))["logits"]
    assert float(jnp.max(jnp.abs(l1 - l2))) > 1e-3
