"""Spans and counters inside the program (``training/timers.py`` is the one
primitive): what a profiler session sees of the engine's step and of the
train loop, what stays when no session runs, and the names the jitted
programs give their phases.  Profiler sessions start inside tests only."""

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.generation import GenerationConfig
from automodel_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from automodel_tpu.serving import DecodeEngine, ServingConfig
from automodel_tpu.serving.scheduler import StepPlan
from automodel_tpu.training import timers as timers_mod
from automodel_tpu.training.timers import SPAN_PREFIX, Timers

CFG = LlamaConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    rope_theta=10000.0, tie_word_embeddings=True,
    max_position_embeddings=128)
PHASES = ["serve_schedule", "serve_assemble", "serve_dispatch",
          "serve_fetch", "serve_finish"]


def _traced(tmp_path, fn):
    """Run ``fn`` under a profiler session; the program's spans as
    ``[(key, start_ns, end_ns, thread line, stats)]`` in time order."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    out.append((e.name[len(SPAN_PREFIX):], int(e.start_ns),
                                int(e.start_ns + e.duration_ns), li,
                                dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


@pytest.fixture(scope="module")
def engine_parts():
    model = LlamaForCausalLM(CFG, param_dtype=jnp.float32,
                             compute_dtype=jnp.float32, remat=False)
    return model, model.init(jax.random.key(0))


def _engine(engine_parts):
    model, params = engine_parts
    return DecodeEngine(
        model, params,
        ServingConfig(kv_block_size=8, max_num_seqs=4, max_model_len=64,
                      prefill_chunk=8),
        generation=GenerationConfig(max_new_tokens=6))


def test_engine_step_spans_and_request_events(engine_parts, tmp_path):
    eng = _engine(engine_parts)
    eng.generate(np.ones((1, 4), np.int64))     # compile outside the trace
    base = eng.stats()
    rng = np.random.default_rng(0)
    rids = []

    def drive():
        for n in (9, 13, 5):
            rids.append(eng.submit(rng.integers(1, 255, n).tolist()))
        eng.run()
        eng.step()                              # an idle call

    spans = _traced(tmp_path, drive)
    stats = eng.stats()
    steps = [s for s in spans if s[0] == "serve_step"]
    ran = stats["steps"] - base["steps"]
    # run() makes one call more than it dispatches steps: the engine looks
    # ahead, so the last call only fetches; then the idle call
    assert len(steps) == ran + 2
    line = steps[0][3]
    dispatched = []
    shapes = []
    for key, s, e, _, _ in steps:
        kids = [k for k in spans if k[0] in PHASES and s <= k[1]
                and k[2] <= e and k[3] == line]
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
        names = [k[0] for k in kids]
        shapes.append(names)
        if "serve_dispatch" in names:
            dispatched.append(kids[2][4])
    # the first call has nothing to fetch yet, the steady ones dispatch
    # step N+1 BEFORE they fetch step N, the last only fetches, then idle
    assert shapes[0] == PHASES[:3]
    assert all(names == PHASES for names in shapes[1:-2])
    assert shapes[-2] == ["serve_schedule", "serve_fetch", "serve_finish"]
    assert shapes[-1] == ["serve_schedule"]
    assert len(dispatched) == ran
    assert [d["ahead"] for d in dispatched] == [0] + [1] * (ran - 1)
    assert stats["ahead_steps"] - base["ahead_steps"] == ran - 1
    assert all(0 <= d["fed_rows"] <= d["rows"] for d in dispatched)
    assert sum(d["fed_rows"] for d in dispatched) > 0
    # the dispatch is a step annotation: its number is ``step_num``, once
    assert [d["step_num"] for d in dispatched] == list(
        range(base["steps"], stats["steps"]))
    assert all(d["_r"] == 1 and "step" not in d for d in dispatched)
    for key in ("rows", "positions", "slots"):
        assert (sum(d[key] for d in dispatched)
                == stats[key + "_sum"] - base[key + "_sum"])
    assert all(d["slots"] == 4 * d["width"] and d["positions"] >= d["rows"]
               >= d["sampled"] >= 0 and d["prefill_rows"] <= d["rows"]
               for d in dispatched)
    # one stamp of each kind per finished request, consistent with each other
    events = {k: {s[4]["rid"]: s for s in spans if s[0] == k}
              for k in ("serve_admit", "serve_first_token",
                        "serve_finish_request")}
    for rid in rids:
        admit = events["serve_admit"][rid]
        first = events["serve_first_token"][rid]
        done = events["serve_finish_request"][rid]
        assert admit[1] <= first[1] <= done[1]
        assert admit[2] - admit[1] < 100_000    # a stamp: no body, ~1 us
        req = eng.requests[rid]
        assert first[4]["queue_us"] == admit[4]["queue_us"]
        assert first[4]["prompt_len"] == len(req.prompt)
        assert first[4]["prefill_steps"] >= -(-len(req.prompt) // 8)
        life_us = 1e6 * (req.finish_time - req.submit_time)
        assert (first[4]["queue_us"] + first[4]["prefill_us"]
                <= life_us + 1)
        assert (first[4]["queue_us"] + first[4]["prefill_us"]
                + done[4]["decode_us"] <= life_us + 2)
        assert done[4]["out_tokens"] == len(req.out_tokens) == 6
        assert done[4]["state"] == "finished"
        assert (req.submit_time <= req.admit_time <= req.first_token_time
                <= req.finish_time)
    for k in events:
        assert set(events[k]) == set(rids)


def test_dispatch_step_numbers_and_kinds_from_the_first_step(engine_parts,
                                                             tmp_path):
    """A fresh engine stepped under a session: one ``serve_dispatch`` per
    dispatched step, numbered 0, 1, 2... by ``step_num``, with
    ``prefill_rows`` > 0 exactly on the mixed steps (the wide program)."""
    eng = _engine(engine_parts)
    rng = np.random.default_rng(1)

    def drive():
        for n in (19, 3):
            eng.submit(rng.integers(1, 255, n).tolist())
        eng.run()
        eng.submit(rng.integers(1, 255, 11).tolist())
        eng.run()

    spans = _traced(tmp_path, drive)
    stats = eng.stats()
    dispatched = [s[4] for s in spans if s[0] == "serve_dispatch"]
    assert [d["step_num"] for d in dispatched] == list(range(stats["steps"]))
    mixed = [d["prefill_rows"] > 0 for d in dispatched]
    assert [d["width"] > 1 for d in dispatched] == mixed
    assert sum(mixed) == stats["mixed_steps"] >= 3
    assert len(mixed) - sum(mixed) == stats["decode_steps"] > 0


def test_step_annotation_carries_its_number(tmp_path):
    """``record(..., step_num=n)`` opens a step annotation: the same
    ``automodel/`` span with its stats and ``step_num``; without it, a plain
    one; both on the host clock."""
    t = Timers()

    def drive():
        with t.record("serve_dispatch", step_num=7, width=32, rows=3):
            pass
        t("dispatch").start(step_num=8)
        t("dispatch").stop()
        with t.record("serve_fetch", rows=3):
            pass

    spans = _traced(tmp_path, drive)
    got = {(k, tuple(sorted(st.items()))) for k, _, _, _, st in spans}
    assert got == {
        ("serve_dispatch", (("_r", 1), ("rows", 3), ("step_num", 7),
                            ("width", 32))),
        ("dispatch", (("_r", 1), ("step_num", 8))),
        ("serve_fetch", (("rows", 3),))}
    assert all(v > 0 for v in t.get_elapsed(reset=False).values())


def test_train_loop_spans(tmp_path):
    from automodel_tpu.config.arg_parser import parse_args_and_load_config
    from automodel_tpu.recipes.llm.train_ft import (
        TrainFinetuneRecipeForNextTokenPrediction,
    )

    yaml = os.path.join(os.path.dirname(__file__), "..", "..", "examples",
                        "llm_finetune", "tiny_llama_mock.yaml")
    cfg = parse_args_and_load_config(
        ["--config", yaml, "--checkpoint.enabled", "false",
         "--step_scheduler.max_steps", "4"])
    recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg).setup()
    assert recipe.dataloader.timers is recipe.timers
    spans = _traced(tmp_path / "trace", recipe.run_train_validation_loop)
    by = {}
    for key, s, e, line, stats in spans:
        by.setdefault(key, []).append((s, e, line, stats))
    main = {line for _, _, line, _ in by["dispatch"]}
    assert len(main) == 1
    for key in ("data_wait", "data_staging_overlap", "dispatch",
                "finalize_metrics", "post_step"):
        assert {line for _, _, line, _ in by[key]} == main, key
    assert [st["step"] for _, _, _, st in by["dispatch"]] == [1, 2, 3, 4]
    assert [st["step"] for _, _, _, st in by["post_step"]] == [1, 2, 3, 4]
    # the loader's busy time lies on its own thread's line
    assert by["input_produce"]
    assert not {line for _, _, line, _ in by["input_produce"]} & main
    # and the same keys accumulated on the host clock
    elapsed = recipe.timers.get_elapsed(reset=False)
    for key in ("data_wait", "dispatch", "finalize_metrics", "post_step",
                "input_produce"):
        assert elapsed[key] > 0, key


def test_timers_without_a_session():
    t = Timers()
    with t.record("a", step=3):
        pass
    t("b").start(rows=2)
    t("b").stop()
    t("c").start()
    t("c").discard()
    t("c").discard()                            # nothing running: a no-op
    t("d").add(0.25)
    t.event("stamp", rid=7, state="finished")   # no session: nothing kept
    got = t.get_elapsed(reset=False)
    assert set(got) == {"a", "b", "c", "d"}
    assert got["a"] > 0 and got["b"] > 0 and got["c"] == 0
    assert got["d"] == 0.25
    assert t.get_elapsed(names=["d"], normalizer=5.0) == {"d": 0.05}
    assert t.get_elapsed(names=["d"]) == {"d": 0.0}     # reading reset it
    assert t.get_elapsed(reset=False)["a"] == got["a"]  # and only it
    with pytest.raises(AssertionError):
        t("a").stop()
    with pytest.raises(AssertionError):
        t("b").start()
        t("b").start()
    # what nothing read is gone
    for name in ("log", "write", "get_global_elapsed", "log_level",
                 "log_option", "_log_levels"):
        assert not hasattr(t, name), name
    assert not hasattr(t("a"), "mean") and not hasattr(t("a"), "_history")
    assert not hasattr(timers_mod, "trace")
    assert SPAN_PREFIX == "automodel/"


def _op_names(text):
    return set(re.findall(r'op_name="([^"]*)"', text))


def test_train_step_names_its_phases():
    import optax

    from automodel_tpu.loss.linear_ce import FusedLinearCrossEntropy
    from automodel_tpu.models.olmo2 import Olmo2Config, Olmo2ForCausalLM
    from automodel_tpu.training.train_step import build_train_step

    cfg = Olmo2Config(vocab_size=256, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=2,
                      num_key_value_heads=2, max_position_embeddings=64)
    model = Olmo2ForCausalLM(cfg, param_dtype=jnp.float32,
                             compute_dtype=jnp.float32)
    params = model.init(jax.random.key(0))
    fns = build_train_step(model, optax.adamw(1e-3),
                           loss_fn=FusedLinearCrossEntropy(chunk_len=16))
    opt = fns.init_opt_state(params)
    ids = jnp.ones((1, 1, 32), jnp.int32)
    batch = {"input_ids": ids, "labels": ids, "segment_ids": ids}
    names = _op_names(fns.train_step.lower(params, opt, batch)
                      .compile().as_text())

    def under(*parts):
        return [n for n in names if all(p in n.split("/") or p in n
                                        for p in parts)]

    for scope in ("grad_accum", "grad_finalize", "optimizer", "metrics"):
        assert any(scope in n.split("/") for n in names), scope
    for scope in ("embed", "layers", "attn", "attn_core", "mlp",
                  "final_norm", "lm_head", "loss", "linear_ce"):
        assert any(re.search(rf"[/(]{scope}[/)]", n) for n in names), scope
    inside = [n for n in names if "grad_accum" in n.split("/")]
    backward = [n for n in inside if "transpose(" in n]
    refwd = [n for n in backward if "rematted_computation" in n]
    forward = [n for n in inside if "transpose(" not in n]
    assert backward and refwd and forward
    assert len(refwd) < len(backward)           # backward proper exists too
    # the re-forward and the backward both run the layer's blocks
    assert any("/attn/" in n for n in refwd)
    assert any("/mlp/" in n and "rematted" not in n for n in backward)
    assert not any("rematted_computation" in n for n in forward)


def test_paged_step_names_its_program_and_phases(engine_parts):
    eng = _engine(engine_parts)
    eng.generate(np.ones((1, 4), np.int64))
    assert sorted(eng.stats()["compiled_widths"]) == [1, 8]
    B, MB = 4, eng.max_blocks_per_seq
    for width in (1, 8):
        i32 = lambda *s: jnp.zeros(s, jnp.int32)
        text = eng.step_fn(width).lower(
            eng.params, eng.pools, i32(B, width), i32(B, width),
            i32(B, width), i32(B, MB), i32(B), i32(B), i32(B),
            i32(B), i32(B), jnp.zeros((B,), jnp.bool_)).compile().as_text()
        assert text.startswith(f"HloModule jit_paged_step_w{width}")
        names = _op_names(text)
        for scope in ("embed", "layers", "attn", "kv_write", "attn_core",
                      "mlp", "final_norm", "lm_head", "sample"):
            assert any(scope in n.split("/") for n in names), scope
        assert all(n.startswith(f"jit(paged_step_w{width})")
                   for n in names if n.startswith("jit("))


# -- the kernels' names, as the chip's compiler gives them --------------------
# XLA:TPU names a Mosaic custom call after the innermost component of its
# scope path.  benchmark/rooflines/{linear_ce,paged_decode}.py find their
# kernels by instruction name, so a scope put round a kernel renames it under
# their feet unless the innermost scope keeps the old name.  The TPU compiler
# is installed here: compile for a described v5e and look.
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _kernels(text):
    """{instruction name: scope path} of the Mosaic custom calls."""
    out = {}
    for line in text.splitlines():
        if "custom-call(" in line and "tpu_custom_call" in line:
            name = re.search(r"%?(\S+) = ", line).group(1)
            out[name] = re.search(r'metadata=\{op_name="([^"]*)"',
                                  line).group(1)
    return out


def test_kernels_keep_their_instruction_names_under_scopes(one_chip):
    from automodel_tpu.ops import linear_ce_kernel, paged_attention_kernel

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(h, w, labels):
        with jax.named_scope("loss"):
            lse, pick = linear_ce_kernel.lse_and_pick(h, w, labels, "pallas")
        return jnp.sum(lse - pick)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        spec((1024, 256), jnp.bfloat16), spec((256, 4096), jnp.bfloat16),
        spec((1024,), jnp.int32)).compile().as_text()
    ce = _kernels(text)
    fwd = [n for n in ce if re.match(r"^jvp_+(\.\d+)?$", n)]
    bwd = [n for n in ce if re.match(r"^transpose_jvp_+(\.\d+)?$", n)]
    assert len(fwd) == 1 and len(bwd) == 2 and len(ce) == 3, ce
    assert all("/linear_ce/" in ce[n] for n in ce)
    assert all("transpose(" in ce[n] for n in bwd)
    assert "transpose(" not in ce[fwd[0]] and "jvp(loss)" in ce[fwd[0]]

    B, S, Hq, D, BS, MB, NB = 8, 1, 4, 128, 16, 8, 64

    def attend(q, kp, vp, layer, tables, lens):
        with jax.named_scope("attn_core"):
            return paged_attention_kernel._paged_decode_impl(
                {}, q, kp, vp, None, None, layer, tables, lens, None)

    text = jax.jit(attend).lower(
        spec((B, S, Hq, D), jnp.bfloat16),
        spec((2, NB, BS, Hq, D), jnp.bfloat16),
        spec((2, NB, BS, Hq, D), jnp.bfloat16), spec((), jnp.int32),
        spec((B, MB), jnp.int32), spec((B,), jnp.int32)).compile().as_text()
    paged = _kernels(text)
    assert len(paged) == 1, paged
    (name, scope), = paged.items()
    assert re.match(r"^closed_call(\.\d+)?$", name)
    assert "/attn_core/paged_decode/" in scope


@pytest.mark.parametrize("hq,window", [(72, 512), (48, None)],
                         ids=["g9_window", "g6_full"])
@pytest.mark.parametrize("width", [1, 128])
def test_the_paged_kernel_compiles_at_laguna_widths(one_chip, hq, window,
                                                    width):
    """Nine (window) and six (full) query heads a kv head over a pool of
    eight kv heads stored as rows, blocks of 128, 32k positions, at decode
    and at the width-128 step: the chip's compiler takes both bodies."""
    from automodel_tpu.ops import paged_attention_kernel

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    B, D, BS, MB, NB = 24, 128, 128, 256, 2560
    text = jax.jit(lambda *a: paged_attention_kernel.paged_decode_pallas(
        *a[:3], None, None, *a[3:], local_window_size=window)).lower(
        spec((B, width, hq, D), jnp.bfloat16),
        spec((2, NB, BS, 8 * D), jnp.bfloat16),
        spec((2, NB, BS, 8 * D), jnp.bfloat16), spec((), jnp.int32),
        spec((B, MB), jnp.int32), spec((B,), jnp.int32),
        spec((B, width), jnp.int32)).compile().as_text()
    assert len(_kernels(text)) == 1


# -- the serving step updates its pools in place ------------------------------
# The stacked KV pools ride the layer scan as carry: each layer scatters into
# them and the paged kernel reads them at a prefetched layer index.  As the
# scan's xs/ys they cost one layer sliced out and one written back per layer
# and two whole-pool copies per step (54 ms of an 88 ms decode step on the
# v5e).  What says the repair holds is the compiled program: its instruction
# list and its temporaries, at shapes where the pools dwarf all else.  The
# pools are lowered as shapes only, at the serving cells' head geometry and
# with planes as large as theirs (4 layers x 11,264 blocks = 16 x 2,816).
_POOL_CFG = LlamaConfig(
    vocab_size=256, hidden_size=256, intermediate_size=512,
    num_hidden_layers=4, num_attention_heads=16, num_key_value_heads=16,
    head_dim=128, rope_theta=10000.0, tie_word_embeddings=True,
    max_position_embeddings=128)
_POOL_BLOCKS = 11264
_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice", "reshape",
          "transpose")


def _pool_sized_moves(text, pools):
    """``[(instruction, "kv" | "scale", "pool" | "layer")]``: what copies,
    slices, update-slices, relays out or allocates something the size of a
    pool plane or of one layer of it (``bitcast`` moves nothing).  A fusion
    counts by what its computation holds."""
    dtypes = {"bfloat16": "bf16", "int8": "s8", "float32": "f32"}
    sizes = {}
    for name, p in pools.items():
        plane = "scale" if name.endswith("_scale") else "kv"
        sizes[(dtypes[str(p.dtype)], p.size)] = (plane, "pool")
        sizes[(dtypes[str(p.dtype)], p.size // p.shape[0])] = (plane, "layer")
    rows, comp = [], None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?(\S+) \(.*\{$", line)
        if head:
            comp = head.group(1)
            continue
        m = re.match(r"^\s+(?:ROOT )?%?(\S+) = (.*?) ([\w\-]+)\(", line)
        if m:
            rows.append((comp, *m.groups(), line))
    fused = {}                     # a fusion's computation -> the fusion
    for comp, name, _, opcode, line in rows:
        if opcode == "fusion":
            called = re.search(r"calls=%?([\w.\-]+)", line).group(1)
            fused[called] = name
    out = set()
    for comp, name, shape, opcode, line in rows:
        alloc = opcode == "custom-call" and "AllocateBuffer" in line
        if not (opcode in _MOVES or alloc):
            continue
        for dtype, dims in re.findall(r"(\w+)\[([\d,]+)\]", shape):
            n = int(np.prod([int(d) for d in dims.split(",")]))
            if (dtype, n) in sizes:
                out.add((fused.get(comp, name), *sizes[(dtype, n)]))
    return sorted(out)


@pytest.mark.parametrize("width,kv_dtype,prefix_caching", [
    (1, None, None), (8, None, None), (1, "int8", None), (8, "int8", None),
    (1, None, "on"), (8, "int8", "on"),
], ids=["w1-bf16", "w8-bf16", "w1-int8", "w8-int8", "w1-bf16-cow",
        "w8-int8-cow"])
def test_paged_step_updates_its_pools_in_place(
        one_chip, monkeypatch, width, kv_dtype, prefix_caching):
    from automodel_tpu.ops.kernel_lib import registry
    from automodel_tpu.serving.kv_cache import pool_bytes

    # the Pallas rung's probe asks for the backend; the program is compiled
    # for the described chip, so the test answers for it
    monkeypatch.setattr(registry, "on_tpu", lambda: True)
    model = LlamaForCausalLM(_POOL_CFG, param_dtype=jnp.bfloat16,
                             compute_dtype=jnp.bfloat16, remat=False)
    eng = DecodeEngine(
        model, model.init(jax.random.key(0)),
        ServingConfig(kv_block_size=16, max_num_seqs=8, max_model_len=128,
                      prefill_chunk=8, num_kv_blocks=16,
                      kv_cache_dtype=kv_dtype, prefix_caching=prefix_caching))

    def spec(a, shape=None):
        return jax.ShapeDtypeStruct(shape or a.shape, a.dtype,
                                    sharding=one_chip)

    pools = {name: spec(p, (p.shape[0], _POOL_BLOCKS, *p.shape[2:]))
             for name, p in eng.pools.items()}
    B, MB = 8, eng.max_blocks_per_seq
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    compiled = eng.step_fn(width).lower(
        jax.tree.map(spec, eng.params), pools,
        i32(B, width), i32(B, width), i32(B, width), i32(B, MB), i32(B),
        i32(B), i32(B), i32(B), i32(B),
        jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one_chip)).compile()
    text = compiled.as_text()

    kernels = _kernels(text)
    assert len(kernels) == 1, kernels
    (name, scope), = kernels.items()
    assert re.match(r"^closed_call(\.\d+)?$", name), kernels
    assert "/attn_core/paged_decode/" in scope, kernels

    temp = compiled.memory_analysis().temp_size_in_bytes
    moves = _pool_sized_moves(text, pools)
    assert not [m for m in moves if m[1] == "kv"], moves
    # An int8 pool's scale planes [L, NB, 16, 16] f32 are the one exception:
    # the TPU keeps them NB-minor, Mosaic reads row-major, so the Pallas rung
    # relays out ONE layer's slice per layer (1/32 of a layer of K).  A whole
    # plane may not move.
    whole = [m for m in moves if m[2] == "pool"]
    if whole and prefix_caching:
        pytest.xfail(f"with cow_copy_blocks compiled in, the scale planes "
                     f"are copied whole once a step: {whole} (PERF.md s7)")
    assert not whole, moves
    assert temp < pool_bytes(pools) / 4, (temp, pool_bytes(pools))


def _one_expert_kernel(names, kernels, text, layers=1):
    """A layer's routed experts are ONE Mosaic call named ``moe_decode``
    under ``moe_experts`` (``benchmark/metrics/experts_kernel_share.serve.py``
    finds it by that name, the two expert rooflines by that scope), ``layers``
    of them in a scan body that holds as many layers, and no expert is run
    under a ``cond`` or a loop of its own beside it."""
    assert len(names) == layers, kernels
    for name in names:
        assert re.match(r"^moe_decode(\.\d+)?$", name), kernels
        assert "/mlp/moe_experts/moe_decode/" in kernels[name], kernels
    for line in text.splitlines():
        if re.search(r" (conditional|while)\(", line):
            assert "/moe_experts/" not in line, line


# -- the latent (MLA) step: one plane, two layer stacks, expert stacks --------
# Kimi-K2's serving step carries ONE latent plane through both layer scans
# and slices an expert's matrices at (layer, expert) where it multiplies
# them.  The same question as above, put to the compiled program: nothing
# the size of the plane, of a layer of it or of a layer's expert stack is
# copied, sliced out or relaid out; one Mosaic call per layer stack, named
# ``mla_decode``.
def _latent_model():
    from automodel_tpu.models.deepseek_v3 import (
        DeepseekV3Config,
        DeepseekV3ForCausalLM,
    )

    cfg = DeepseekV3Config(
        vocab_size=256, hidden_size=256, intermediate_size=512,
        num_hidden_layers=4, num_attention_heads=16, num_key_value_heads=16,
        q_lora_rank=128, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, n_routed_experts=32,
        num_experts_per_tok=4, moe_intermediate_size=768,
        first_k_dense_replace=1, held_experts=[8, 8],
        tie_word_embeddings=False, max_position_embeddings=256,
        moe_capacity_factor=None)
    return DeepseekV3ForCausalLM(cfg, param_dtype=jnp.bfloat16,
                                 compute_dtype=jnp.bfloat16, remat=False)


@pytest.mark.parametrize("width", [1, 8], ids=["w1", "w8"])
def test_latent_step_updates_its_plane_in_place(one_chip, monkeypatch, width):
    from automodel_tpu.ops.kernel_lib import registry
    from automodel_tpu.serving.kv_cache import pool_bytes

    monkeypatch.setattr(registry, "on_tpu", lambda: True)
    model = _latent_model()
    params = model.abstract_params()
    eng = DecodeEngine(
        model, params,
        ServingConfig(kv_block_size=16, max_num_seqs=8, max_model_len=128,
                      prefill_chunk=8, num_kv_blocks=16))
    assert sorted(eng.pools) == ["kv"] and eng.pools["kv"].shape[-1] == 640

    def spec(a, shape=None):
        return jax.ShapeDtypeStruct(shape or a.shape, a.dtype,
                                    sharding=one_chip)

    pools = {name: spec(p, (p.shape[0], _POOL_BLOCKS, *p.shape[2:]))
             for name, p in eng.pools.items()}
    B, MB = 8, eng.max_blocks_per_seq
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    compiled = eng.step_fn(width).lower(
        jax.tree.map(spec, params), pools,
        i32(B, width), i32(B, width), i32(B, width), i32(B, MB), i32(B),
        i32(B), i32(B), i32(B), i32(B),
        jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one_chip)).compile()
    text = compiled.as_text()

    kernels = _kernels(text)
    mla = {n: s for n, s in kernels.items() if n.startswith("mla_decode")}
    assert len(mla) == 2, kernels           # the dense stack's, the experts'
    for name, scope in mla.items():
        assert re.match(r"^mla_decode(\.\d+)?$", name), kernels
        assert "/attn/attn_core/mla_decode/" in scope, kernels
    _one_expert_kernel(set(kernels) - set(mla), kernels, text)
    for scope_name in ("mla_latent_write", "mla_absorb_q", "mla_out",
                       "moe_router", "moe_experts", "moe_shared",
                       "dense_mlp"):
        assert f"/{scope_name}/" in text, scope_name

    assert not _pool_sized_moves(text, pools), _pool_sized_moves(text, pools)
    # the expert stacks [3, 8, 256, 768] stay where they are: the step reads
    # one expert's matrices out of the stacks of all layers; handed to the
    # expert loops as one layer's slice, the stack was copied out per layer
    stacks = {"e": jax.ShapeDtypeStruct((3, 8 * 256 * 768), jnp.bfloat16)}
    assert not _pool_sized_moves(text, stacks), _pool_sized_moves(text, stacks)
    assert compiled.memory_analysis().temp_size_in_bytes \
        < pool_bytes(pools) / 4


# -- the window/full step: two block groups, 8 small experts a layer ---------
# SmallThinker's serving step walks two pools under one period scan and runs
# a layer's routed experts as one kernel that addresses the stacks of all
# layers at (layer, expert): the same questions, put to its program.
def _window_full_model():
    from automodel_tpu.models.smallthinker import (
        SmallThinkerConfig,
        SmallThinkerForCausalLM,
    )

    cfg = SmallThinkerConfig(
        vocab_size=256, hidden_size=256, num_hidden_layers=8,
        num_attention_heads=4, num_key_value_heads=2, head_dim=128,
        moe_num_primary_experts=8, moe_num_active_primary_experts=2,
        moe_ffn_hidden_size=384, rope_layout=(0, 1, 1, 1) * 2,
        sliding_window_layout=(0, 1, 1, 1) * 2, sliding_window_size=32,
        max_position_embeddings=256)
    return SmallThinkerForCausalLM(cfg, param_dtype=jnp.bfloat16,
                                   compute_dtype=jnp.bfloat16, remat=False)


@pytest.mark.parametrize("width", [1, 8], ids=["w1", "w8"])
def test_window_full_step_runs_its_experts_as_one_kernel(
        one_chip, monkeypatch, width):
    from automodel_tpu.ops.kernel_lib import registry
    from automodel_tpu.serving.kv_cache import pool_bytes

    monkeypatch.setattr(registry, "on_tpu", lambda: True)
    model = _window_full_model()
    params = model.abstract_params()
    eng = DecodeEngine(
        model, params,
        ServingConfig(kv_block_size=16, max_num_seqs=8, max_model_len=128,
                      prefill_chunk=8,
                      num_kv_blocks={"full": 16, "window": 16}))
    assert sorted(eng.pools) == ["full", "window"]

    def spec(a, shape=None):
        return jax.ShapeDtypeStruct(shape or a.shape, a.dtype,
                                    sharding=one_chip)

    pools = {g: {name: spec(p, (p.shape[0], _POOL_BLOCKS, *p.shape[2:]))
                 for name, p in planes.items()}
             for g, planes in eng.pools.items()}
    compiled = eng.step_fn(width).lower(
        jax.tree.map(spec, params), pools,
        *jax.tree.map(lambda a: spec(jnp.asarray(a)), eng._assemble(
            StepPlan(rows=[None] * 8, step_width=width)))).compile()
    text = compiled.as_text()

    kernels = _kernels(text)
    paged = {n for n in kernels if n.startswith("paged_decode")}
    assert len(paged) == 4, kernels         # a period: one full, three window
    _one_expert_kernel(set(kernels) - paged, kernels, text, layers=4)
    flat = {f"{g}.{name}": p for g, planes in pools.items()
            for name, p in planes.items()}
    assert not _pool_sized_moves(text, flat), _pool_sized_moves(text, flat)
    # the expert stacks [8, 8, 256, 384] stay where they are
    stacks = {"e": jax.ShapeDtypeStruct((8, 8 * 256 * 384), jnp.bfloat16)}
    assert not _pool_sized_moves(text, stacks), _pool_sized_moves(text, stacks)
    assert compiled.memory_analysis().temp_size_in_bytes \
        < sum(pool_bytes(p) for p in pools.values()) / 4


# -- the retention step: per-sequence state planes, no block pool ------------
# Brumby's serving step carries two state planes through its layer scan and
# the retention kernels read and write a (row, kv head) tile of them in place
# (``input_output_aliases``) at a prefetched layer.  The same question again:
# nothing the size of a plane or of a layer of it is copied, sliced out or
# relaid out; one Mosaic call a step program, named ``retention_decode`` in
# the decode program and ``retention_chunk`` in the wider one.
@pytest.mark.parametrize("width,kernel", [(1, "retention_decode"),
                                          (8, "retention_chunk")],
                         ids=["w1", "w8"])
def test_retention_step_updates_its_state_planes_in_place(
        one_chip, monkeypatch, width, kernel):
    from automodel_tpu.models.brumby import BrumbyConfig, BrumbyForCausalLM
    from automodel_tpu.ops.kernel_lib import registry
    from automodel_tpu.serving.kv_cache import pool_bytes

    monkeypatch.setattr(registry, "on_tpu", lambda: True)
    cfg = BrumbyConfig(
        vocab_size=256, hidden_size=256, intermediate_size=512,
        num_hidden_layers=4, num_attention_heads=10, num_key_value_heads=2,
        head_dim=128, rope_theta=1e6, max_position_embeddings=128)
    model = BrumbyForCausalLM(cfg, param_dtype=jnp.bfloat16,
                              compute_dtype=jnp.bfloat16, remat=False)
    params = model.abstract_params()
    eng = DecodeEngine(model, params, ServingConfig(
        max_num_seqs=8, max_model_len=4096, prefill_chunk=8))
    assert sorted(eng.pools) == ["norm", "state"]
    assert eng.pools["state"].shape == (4, 8, 2, 65, 128, 128)
    assert eng.max_blocks_per_seq == 1 and eng.allocator.num_blocks == 2

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    pools = jax.tree.map(spec, eng.pools)
    B = 8
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    compiled = eng.step_fn(width).lower(
        jax.tree.map(spec, params), pools,
        i32(B, width), i32(B, width), i32(B, width), i32(B, 1), i32(B),
        i32(B), i32(B), i32(B), i32(B),
        jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one_chip)).compile()
    text = compiled.as_text()
    assert text.startswith(f"HloModule jit_paged_step_w{width}")

    kernels = _kernels(text)
    assert len(kernels) == 1, kernels
    (name, scope), = kernels.items()
    assert re.match(rf"^{kernel}(\.\d+)?$", name), kernels
    assert f"/attn/attn_core/{kernel}/" in scope, kernels
    for scope_name in ("retention_gate", "retention_out", "state_reset"):
        assert f"/{scope_name}/" in text, scope_name

    assert not _pool_sized_moves(text, pools), _pool_sized_moves(text, pools)
    assert compiled.memory_analysis().temp_size_in_bytes \
        < pool_bytes(pools) / 4


# -- gated attention in stacks by kind: the gate's scope, the pairs counted --
# Laguna's step runs a per-head output gate inside each kind's attention
# scope, and stamps per block group the (query, key) pairs one layer
# computed, which the benchmark's chunked paged roofline reads.
def _gated_model():
    from automodel_tpu.models.laguna import LagunaConfig, LagunaForCausalLM

    cfg = LagunaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=96,
        num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        mlp_only_layers=(0,), gating="per-head", sliding_window=8,
        layer_types=("full_attention",) + ("sliding_attention",) * 3
        + ("full_attention",), num_attention_heads_per_layer=(4, 6, 6, 6, 4),
        max_position_embeddings=128)
    model = LagunaForCausalLM(cfg, param_dtype=jnp.float32,
                              compute_dtype=jnp.float32, remat=False)
    return model, model.init(jax.random.key(0))


def test_gated_step_names_the_gate_under_both_attention_scopes():
    model, params = _gated_model()
    eng = DecodeEngine(model, params, ServingConfig(
        kv_block_size=4, max_num_seqs=2, max_model_len=64, prefill_chunk=4))
    for width in (1, 4):
        names = _op_names(eng.step_fn(width).lower(
            eng.params, eng.pools, *eng._assemble(
                StepPlan(rows=[None] * 2, step_width=width))
        ).compile().as_text())
        for kind in ("attn_full", "attn_window"):
            assert any(f"/{kind}/attn_gate/" in n for n in names), kind
        for scope in ("moe_router", "moe_experts", "moe_shared",
                      "dense_mlp"):
            assert any(f"/{scope}/" in n for n in names), scope


def test_kv_read_counts_the_pairs_each_group_computes():
    """Two rows, a window of 8, chunks of 4: each step's ``full_pairs`` and
    ``window_pairs`` are the keys each new position sees, summed over the
    rows; a hand count on the step whose chunk crosses the window."""
    model, params = _gated_model()
    timers, seen, plans = Timers(), [], []
    real = timers.event
    timers.event = lambda name, **st: (seen.append((name, st)),
                                       real(name, **st))[1]
    eng = DecodeEngine(model, params, ServingConfig(
        kv_block_size=4, max_num_seqs=2, max_model_len=64, prefill_chunk=4),
        timers=timers, generation=GenerationConfig(
            max_new_tokens=4, do_sample=False, eos_token_id=None))
    eng.lookahead = 0
    inner = eng.scheduler.schedule

    def schedule(*a, **k):
        plan = inner(*a, **k)
        if plan is not None and plan.active:
            plans.append([(w.start_pos, len(w.tokens)) for w in plan.active])
        return plan

    eng.scheduler.schedule = schedule
    rng = np.random.default_rng(2)
    for n in (11, 3):
        eng.submit(rng.integers(1, 255, n).tolist(), max_new_tokens=4)
    eng.run()
    reads = [st for name, st in seen if name == "serve_kv_read"]
    assert len(reads) == len(plans) == eng.steps_run
    for rows, st in zip(plans, reads):
        assert st["full_pairs"] == sum(p + 1 for s, n in rows
                                       for p in range(s, s + n))
        assert st["window_pairs"] == sum(min(p + 1, 8) for s, n in rows
                                         for p in range(s, s + n))
    # the first step: positions 0-3 of one row and 0-2 of the other
    assert plans[0] == [(0, 4), (0, 3)]
    assert (reads[0]["full_pairs"], reads[0]["window_pairs"]) == (16, 16)
    # the prompt's last chunk, positions 8-10: 9 + 10 + 11 keys a full
    # layer, 8 each a window layer
    at = next(i for i, rows in enumerate(plans) if (8, 3) in rows)
    other = sum(p + 1 for s, n in plans[at] if (s, n) != (8, 3)
                for p in range(s, s + n))
    other_w = sum(min(p + 1, 8) for s, n in plans[at] if (s, n) != (8, 3)
                  for p in range(s, s + n))
    assert reads[at]["full_pairs"] == 30 + other
    assert reads[at]["window_pairs"] == 24 + other_w
