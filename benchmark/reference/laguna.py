"""The family ``laguna`` (a configuration's ``model_type`` finds this file):
poolside's Laguna-S-2.1 decoder as ONE expert-parallel share of a layer —
which weights the share holds and where each sits in the program's
parameter tree, the work a step requires of it, and its plain reference.
Serving only (training fits no cut inside the guide's floors: PERF.md
section 4).

The plain reference: float32 at ``highest`` matmul precision, ``jax.numpy``
and ``jax.lax`` only, no cache, no kernels.  It imports nothing of the
program.  To fit one chip beside nothing else with 5.6 B parameters and a
29k-token request, attention runs a block of queries at a time (each
block's queries, gate and output projection computed there), matrices are
upcast one layer at a time and the experts one expert at a time, and each
held expert multiplies only the rows that chose it.

The equations, for a layer of kind ``K`` (full or window, ``layer_types``)
on a stream ``x [T, H]``, eps ``rms_norm_eps``, ``G = Hq_K / Hk``::

    u  = rmsnorm(x)
    q  = u Wq [T, Hq_K, d]    k = u Wk [T, Hk, d]    v = u Wv [T, Hk, d]
    q, k = rmsnorm_head(q), rmsnorm_head(k)                 # inferred
    q, k = rope_K(q, k)   # full: YaRN on channels 0..d/2-1; window: all d
    visible(i, j) = j <= i  and (K == window  =>  i - j < sliding_window)
    a_h = softmax(q_h k_{h//G}^T / sqrt(d) over visible) v_{h//G}
    g   = sigmoid(u Wg) [T, Hq_K]                               # inferred
    h   = x + concat_h(g_h * a_h) Wo
    m   = rmsnorm(h)
    dense layers (mlp_layer_types "dense"):  y = SwiGLU(m)
    routed: r = m Wr [T, E];  S = top-k of r;  p = softmax(r[S]) * scale
            y = sum_{e in S and held} p_e SwiGLU_e(m) + SwiGLU_shared(m)
    x' = h + y
    logits = rmsnorm(x_L) W_head                             # untied

``config.json`` does not settle these, and the configuration's file lists
each under ``assumed``; they are inferences:

* the gate (``gating: per-head``) is the headwise gate of Qiu et al. 2025,
  "Gated Attention for Large Language Models" (arXiv:2505.06708): a sigmoid
  of ``u``, one scalar a query head, on the head's output before ``Wo``,
  no bias (``attention_bias: false``);
* the router is softmax over the chosen logits times
  ``moe_routed_scaling_factor`` (equal to a full softmax renormalised by
  ``norm_topk_prob``): the config's key names are the Qwen-MoE family's,
  whose score is softmax.  The alternative, DeepSeek-V3's sigmoid score
  under the same 2.5 / ``norm_topk_prob`` convention, was not taken because
  no key names a sigmoid score or a selection bias.  No selection bias, no
  cap (``moe_router_logit_softcapping: 0``), the shared expert ungated;
* a per-head RMSNorm on q and k before RoPE (Qwen3's convention in the same
  key family; the alternative is none);
* rotate-half pairing ``(i, i + dim/2)`` over the rotating channels; YaRN's
  ``attention_factor`` multiplies their cos and sin, so it scales those
  channels only;
* SwiGLU everywhere, no biases.

**The held share:** the configuration holds experts ``held_experts[0] ..
+ num_experts`` of ``published.num_experts``; the router stays published
wide, the sum runs over ``chosen & held`` only, the normalisation over all
the chosen, and that partial result goes on to the next layer (on one chip
the layer runs without its exchange).  :func:`routed_experts` takes the
stack it holds and its first id, which is what the share test varies.

*What a served token can show, and what it cannot.*  Top-k is a step
function: where a HELD expert's logit lies within round-off of the
selection boundary (midway between the k-th and the (k+1)-th logit), the
bfloat16 program and this float32 reference may choose differently, and one
expert more or less moves the logits by more than any rounding does.
Which way such a tie falls says nothing of the program.  As
``reference/kimi_k2.py`` and ``reference/smallthinker.py`` do,
:func:`served_token_gaps` compares every UNTIED token in full and the tied
tokens of a request through one number, the ``TIED_QUANTILE`` of their
gaps; a token is tied where in any routed layer a held expert's logit lies
within ``TIE`` of the root-mean-square of its logits from the boundary.
Here nearly every token is: four routed layers, each with 128 held experts
round a top-10 boundary among 256, put one held logit within 3 % of the
rms of the boundary in 97-99 % of them.  Round-off flips a choice where the
margin is ~1e-3 or less, and one expert more or less, at a routed scale of
2.5, moves the logits by 0.1-0.3: 1-9 % of a sound run's tied tokens
read over ``FLIP_GAP`` (PERF.md section 2).  The 95th percentile of the other
two families lands among those flips and reads 0.008-0.059 from run to run,
so the quantile here is the 90th, above most requests' flipped share; a
float32 program agrees with this reference to 7e-6 of the logits (a CPU run
at the published attention widths, PERF.md section 2), so what the bulk
shows is the arithmetic.

The 90th percentile cannot see a fault that moves under a tenth of the
tied tokens, nor one that moves many by less than the limit, so a second
number stands beside it: the share of a request's tied tokens that read
over ``FLIP_GAP``.  Round-off alone keeps it under 9 %, and the program's
own int8 path reads 43-57 %; where it passes ``FLIP_SHARE`` the ties no
longer account for the gaps, and each tied token carries the widest of
theirs instead of the percentile.  What stays unseen is a fault confined
to fewer tokens than round-off flips itself.

``attention_pair_flops`` is ONE number a (query, key) pair and credits the
FULL layers only.  The runner counts each position's pairs over its whole
causal context, one number for every layer; at the cell's mix (prefill
positions see ~4.3k keys on average) a window layer's pairs past its 512
are ~90 % of what it would count, so crediting all layers would inflate
``mfu.serve`` by ~30 %; leaving the window layers out under-credits it by
~2 %.  The count by kind is ``chunked_paged_roofline.serve``'s
(``rooflines/chunked_paged_attention.py``, from the program's own pairs).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark import weights
from benchmark.weights import head_dim

F32 = jnp.float32
FULL, WINDOW = "full", "window"
KIND = {"full_attention": FULL, "sliding_attention": WINDOW}
Q_BLOCK = 32            # queries whose scores are live at a time
TOKEN_BLOCK = 256       # tokens a dense MLP or an expert multiplies at a time
MAKE_PIECE = 1 << 26    # elements drawn at a time (256 MB of float32)
TIE = 3e-2              # a held expert's logit this near the boundary, as a
                        # share of the token's rms logit: round-off chooses
TIED_QUANTILE = 0.9     # of a request's tied tokens' gaps: stands for each
                        # (above the share of them that round-off flips)
MIN_TIED = 40           # fewer: the quantile is their max; they are left out
FLIP_GAP = 0.02         # a tied token's gap over this counts as flipped
FLIP_SHARE = 0.2        # flipped tied tokens past this share of a request's:
                        # more than round-off flips, so the ties stand for
                        # their widest gap (PERF.md section 2's readings)

ATTN = ("input_norm", "q_proj", "k_proj", "v_proj", "q_norm", "k_norm",
        "gate", "o_proj", "post_norm")
DENSE = ATTN + ("mlp_gate", "mlp_up", "mlp_down")
ROUTED = ATTN + ("router", "e_gate", "e_up", "e_down", "s_gate", "s_up",
                 "s_down")
PREFIX = {"dense": "d.", FULL: "f.", WINDOW: "w."}


# ---------------------------------------------------------------------------
# The family's weights, its layout in the program, its required work
# ---------------------------------------------------------------------------
def layer_kinds(cfg: Dict[str, Any]) -> Tuple[str, ...]:
    return tuple(KIND[t] for t in cfg["layer_types"])


def dense_count(cfg: Dict[str, Any]) -> int:
    """The leading layers whose MLP is dense."""
    types = cfg["mlp_layer_types"]
    return next((l for l, t in enumerate(types) if t != "dense"), len(types))


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    kinds = layer_kinds(cfg)
    heads = cfg["num_attention_heads_per_layer"]
    hq = {k: next(h for h, t in zip(heads, kinds) if t == k)
          for k in set(kinds)}
    kd = dense_count(cfg)
    pub = cfg.get("published") or {}
    return dict(
        H=cfg["hidden_size"], D=head_dim(cfg), Hk=cfg["num_key_value_heads"],
        Hq_full=hq.get(FULL, 0), Hq_window=hq.get(WINDOW, 0),
        I=cfg["intermediate_size"], Im=cfg["moe_intermediate_size"],
        Is=cfg["shared_expert_intermediate_size"],
        E=pub.get("num_experts", cfg["num_experts"]),
        held=cfg["num_experts"], first=(cfg.get("held_experts") or [0])[0],
        k=cfg["num_experts_per_tok"], V=cfg["vocab_size"],
        L=cfg["num_hidden_layers"], kd=kd, n_moe=len(kinds) - kd,
        n_full=kinds.count(FULL), n_window=kinds.count(WINDOW),
        routed_full=kinds[kd:].count(FULL),
        routed_window=kinds[kd:].count(WINDOW),
        W=int(cfg.get("sliding_window") or 0))


def leaf_shapes(cfg: Dict[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, "matrix"|"norm"), in a fixed order.  ``d.*`` leaves
    stack the dense layers, ``f.*`` / ``w.*`` the routed full / window
    layers, on their first axis."""
    z = sizes(cfg)
    H, D, Hk = z["H"], z["D"], z["Hk"]
    kinds = layer_kinds(cfg)

    def attn(p, n, hq):
        return {
            p + "input_norm": ((n, H), "norm"),
            p + "q_proj": ((n, H, hq * D), "matrix"),
            p + "k_proj": ((n, H, Hk * D), "matrix"),
            p + "v_proj": ((n, H, Hk * D), "matrix"),
            p + "q_norm": ((n, D), "norm"),
            p + "k_norm": ((n, D), "norm"),
            p + "gate": ((n, H, hq), "matrix"),
            p + "o_proj": ((n, hq * D, H), "matrix"),
            p + "post_norm": ((n, H), "norm"),
        }

    out = {"embed": ((z["V"], H), "matrix")}
    if z["kd"]:
        n = z["kd"]
        out.update(attn("d.", n, z["Hq_" + kinds[0]]))
        out.update({"d.mlp_gate": ((n, H, z["I"]), "matrix"),
                    "d.mlp_up": ((n, H, z["I"]), "matrix"),
                    "d.mlp_down": ((n, z["I"], H), "matrix")})
    for kind in (FULL, WINDOW):
        n, p = z["routed_" + kind], PREFIX[kind]
        if not n:
            continue
        out.update(attn(p, n, z["Hq_" + kind]))
        out.update({
            p + "router": ((n, H, z["E"]), "matrix"),
            p + "e_gate": ((n, z["held"], H, z["Im"]), "matrix"),
            p + "e_up": ((n, z["held"], H, z["Im"]), "matrix"),
            p + "e_down": ((n, z["held"], z["Im"], H), "matrix"),
            p + "s_gate": ((n, H, z["Is"]), "matrix"),
            p + "s_up": ((n, H, z["Is"]), "matrix"),
            p + "s_down": ((n, z["Is"], H), "matrix"),
        })
    out["final_norm"] = ((H,), "norm")
    out["lm_head"] = ((H, z["V"]), "matrix")
    return out


def make(cfg: Dict[str, Any], words) -> Dict[str, Any]:
    """The flat dict of this configuration's weights, as ``weights.make``
    draws them (normal(0, 0.02) matrices, 1 + 0.1 normal norm weights,
    rounded to bfloat16) but PIECE BY PIECE: a window stack's experts are
    1.2 G elements, and drawn whole their float32 temporaries alone would
    pass the chip's memory.  A leaf over ``MAKE_PIECE`` elements is drawn
    slice by slice of its leading axes, each slice under a key of its own.
    ``assumed.qk_gain`` (a toy's file only) multiplies ``q_proj`` and
    ``k_proj``: at a toy's width a score's spread is far under the
    published widths' and attention would be uniform.  Traceable."""
    key = jax.random.fold_in(jax.random.key(words[0]), words[1])

    def draw(k, shape, kind):
        z = jax.random.normal(k, shape, F32)
        w = (z * weights.MATRIX_STD if kind == "matrix"
             else 1.0 + weights.NORM_STD * z)
        return lax.reduce_precision(w, 8, 7).astype(jnp.bfloat16)

    out = {}
    for i, (name, (shape, kind)) in enumerate(leaf_shapes(cfg).items()):
        k = jax.random.fold_in(key, i)
        lead = 0
        while math.prod(shape[lead:]) > MAKE_PIECE and lead < len(shape) - 2:
            lead += 1
        if not lead:
            out[name] = draw(k, shape, kind)
            continue
        pieces = lax.map(
            lambda j: draw(jax.random.fold_in(k, j), shape[lead:], kind),
            jnp.arange(math.prod(shape[:lead]), dtype=jnp.uint32))
        out[name] = pieces.reshape(shape)
    gain = (cfg.get("assumed") or {}).get("qk_gain")
    if gain:
        for name in [n for n in out if n.endswith(("q_proj", "k_proj"))]:
            out[name] = lax.reduce_precision(
                out[name].astype(F32) * gain, 8, 7).astype(jnp.bfloat16)
    return out


def to_program_tree(flat: Dict[str, Any]) -> Dict[str, Any]:
    """The flat dict in the layout of ``automodel_tpu.models.laguna``
    (stacks by kind, ``[in, out]`` kernels, ``[held, ...]`` expert stacks) —
    the one place the benchmark names the program's parameter tree."""
    kernel = lambda n: {"kernel": flat[n]}
    weight = lambda n: {"weight": flat[n]}

    def block(p):
        return {
            "input_layernorm": weight(p + "input_norm"),
            "post_attention_layernorm": weight(p + "post_norm"),
            "self_attn": {"q_proj": kernel(p + "q_proj"),
                          "k_proj": kernel(p + "k_proj"),
                          "v_proj": kernel(p + "v_proj"),
                          "o_proj": kernel(p + "o_proj"),
                          "q_norm": weight(p + "q_norm"),
                          "k_norm": weight(p + "k_norm"),
                          "attn_gate": kernel(p + "gate")},
        }

    tree = {"embed_tokens": {"embedding": flat["embed"]},
            "norm": weight("final_norm"), "lm_head": kernel("lm_head")}
    if "d.mlp_gate" in flat:
        tree["dense_layers"] = dict(block("d."), mlp={
            "gate_proj": kernel("d.mlp_gate"), "up_proj": kernel("d.mlp_up"),
            "down_proj": kernel("d.mlp_down")})
    for kind in (FULL, WINDOW):
        p = PREFIX[kind]
        if p + "router" not in flat:
            continue
        tree["layers_" + kind] = dict(block(p), mlp={
            "router": kernel(p + "router"),
            "experts": {"gate_proj": kernel(p + "e_gate"),
                        "up_proj": kernel(p + "e_up"),
                        "down_proj": kernel(p + "e_down")},
            "shared_expert": {"gate_proj": kernel(p + "s_gate"),
                              "up_proj": kernel(p + "s_up"),
                              "down_proj": kernel(p + "s_down")}})
    return tree


def from_program_tree(tree: Dict[str, Any]) -> Dict[str, Any]:
    """Inverse of :func:`to_program_tree`."""
    def block(t, p):
        a = t["self_attn"]
        return {p + "input_norm": t["input_layernorm"]["weight"],
                p + "q_proj": a["q_proj"]["kernel"],
                p + "k_proj": a["k_proj"]["kernel"],
                p + "v_proj": a["v_proj"]["kernel"],
                p + "q_norm": a["q_norm"]["weight"],
                p + "k_norm": a["k_norm"]["weight"],
                p + "gate": a["attn_gate"]["kernel"],
                p + "o_proj": a["o_proj"]["kernel"],
                p + "post_norm": t["post_attention_layernorm"]["weight"]}

    out = {"embed": tree["embed_tokens"]["embedding"]}
    if "dense_layers" in tree:
        d = tree["dense_layers"]
        out.update(block(d, "d."))
        out.update({"d.mlp_gate": d["mlp"]["gate_proj"]["kernel"],
                    "d.mlp_up": d["mlp"]["up_proj"]["kernel"],
                    "d.mlp_down": d["mlp"]["down_proj"]["kernel"]})
    for kind in (FULL, WINDOW):
        if "layers_" + kind not in tree:
            continue
        t, p = tree["layers_" + kind], PREFIX[kind]
        out.update(block(t, p))
        mlp = t["mlp"]
        out.update({
            p + "router": mlp["router"]["kernel"],
            p + "e_gate": mlp["experts"]["gate_proj"]["kernel"],
            p + "e_up": mlp["experts"]["up_proj"]["kernel"],
            p + "e_down": mlp["experts"]["down_proj"]["kernel"],
            p + "s_gate": mlp["shared_expert"]["gate_proj"]["kernel"],
            p + "s_up": mlp["shared_expert"]["up_proj"]["kernel"],
            p + "s_down": mlp["shared_expert"]["down_proj"]["kernel"]})
    out["final_norm"] = tree["norm"]["weight"]
    out["lm_head"] = tree["lm_head"]["kernel"]
    return out


def model_config(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The keys of a configuration file that ``build_model`` takes: the
    router at the PUBLISHED expert count, and beside it the share this
    configuration holds (the file's own ``num_experts``)."""
    keys = ("model_type", "vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "max_position_embeddings",
            "attention_bias", "rms_norm_eps", "num_experts_per_tok",
            "moe_intermediate_size", "shared_expert_intermediate_size",
            "norm_topk_prob", "decoder_sparse_step", "mlp_only_layers",
            "tie_word_embeddings", "gating", "sliding_window",
            "rope_parameters", "layer_types", "mlp_layer_types",
            "gating_types", "moe_routed_scaling_factor",
            "num_attention_heads_per_layer", "moe_router_logit_softcapping",
            "moe_apply_router_weight_on_input", "torch_dtype")
    out = {k: cfg[k] for k in keys if k in cfg}
    z = sizes(cfg)
    out["num_experts"] = z["E"]
    if z["held"] != z["E"]:
        out["held_experts"] = [z["first"], z["held"]]
    return out


def expert_params(cfg: Dict[str, Any]) -> int:
    """One expert's three matrices: 9,437,184 at the published widths."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def attention_params(cfg: Dict[str, Any], kind: str) -> int:
    """q, k, v, o and the gate of one layer of a kind (norms left out):
    44,187,648 full and 63,135,744 window at the published widths."""
    z = sizes(cfg)
    hq = z["Hq_" + kind]
    return (z["H"] * (hq + 2 * z["Hk"]) * z["D"] + hq * z["D"] * z["H"]
            + z["H"] * hq)


def matmul_params(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameters that sit in a matrix product for ONE position, summed
    over the layers, and in the head.  A routed layer counts its attention,
    the router, the shared expert and the share of the k chosen experts
    that this configuration holds on average (k * held / published); a
    dense layer its attention and its MLP."""
    z = sizes(cfg)
    H, expert = z["H"], expert_params(cfg)
    layers = 0
    for l, kind in enumerate(layer_kinds(cfg)):
        layers += attention_params(cfg, kind)
        if l < z["kd"]:
            layers += 3 * H * z["I"]
        else:
            layers += (H * z["E"] + 3 * H * z["Is"]
                       + z["k"] * z["held"] * expert // z["E"])
    return {"layer": layers // z["L"], "layers": layers,
            "head": H * z["V"], "expert": expert}


def attention_pair_flops(cfg: Dict[str, Any]) -> int:
    """Forward FLOPs per (query, key) pair, QK^T and PV, over the FULL
    layers only (the module docstring says why and by how much)."""
    z = sizes(cfg)
    return 4 * z["Hq_full"] * z["D"] * z["n_full"]


def kv_bytes_per_token_layer(cfg: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """K and V of one token in one layer: 4,096 B at the published widths."""
    z = sizes(cfg)
    return 2 * z["Hk"] * z["D"] * dtype_bytes


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------
def highest(fn):
    """Trace ``fn`` with float32 matmuls at full precision."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope_table(cfg: Dict[str, Any], kind: str):
    """``(inverse frequencies [dim/2], cos/sin factor, dim)`` of a kind's
    table, computed here from the file's ``rope_parameters``: YaRN blends
    ``theta^(-2j/dim)`` with the same over ``factor`` along a ramp between
    floor(low) and ceil(high) of the correction range."""
    rp = cfg["rope_parameters"]["full_attention" if kind == FULL
                                else "sliding_attention"]
    d = int(head_dim(cfg) * float(rp.get("partial_rotary_factor", 1.0)))
    theta = float(rp["rope_theta"])
    base = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    if rp.get("rope_type", "default") == "default":
        return base, 1.0, d
    factor = float(rp["factor"])
    old = float(rp["original_max_position_embeddings"])

    def correction(rotations):
        return d * math.log(old / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction(float(rp["beta_fast"]))), 0)
    high = min(math.ceil(correction(float(rp["beta_slow"]))), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (base / factor * ramp + base * (1 - ramp),
            float(rp["attention_factor"]), d)


def rope(x, pos, inv_freq, factor, dim):
    """x [T, heads, d]: channels (i, i + dim/2), i < dim/2, rotate by pos *
    inv_freq[i], their cos and sin times ``factor``; channels from ``dim``
    on pass as they are."""
    ang = pos.astype(F32)[:, None] * jnp.asarray(inv_freq, F32)[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2, rest = x[..., :dim // 2], x[..., dim // 2:dim], x[..., dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def attention_block(p, h, dims, window: int):
    """``h + concat_h(g_h * a_h) Wo`` for one sequence ``h [T, H]``: keys
    and values for every position, then a block of ``Q_BLOCK`` queries at a
    time (their projection, scores, gate and output projection).  A window
    layer's block reads the ``window + Q_BLOCK`` keys that can be visible
    to it; a full layer's reads all of them, masked causally.  A ``T`` past
    a multiple of ``Q_BLOCK`` is padded on the right (causal: the pad
    changes nothing before it) and cut off again."""
    hq, hk, d, eps, (inv, factor, dim) = dims
    n = h.shape[0]
    qb = min(Q_BLOCK, n)
    t = -(-n // qb) * qb
    h = jnp.pad(h, ((0, t - n), (0, 0)))
    g = hq // hk
    u = rms_norm(h, p["input_norm"], eps)
    pos = jnp.arange(t, dtype=jnp.int32)
    k = rms_norm((u @ p["k_proj"]).reshape(t, hk, d), p["k_norm"], eps)
    k = rope(k, pos, inv, factor, dim)
    v = (u @ p["v_proj"]).reshape(t, hk, d)
    span = t if not window else min(t, window + qb)
    if window:
        # keys [i0 - window, i0 + qb) of a block at i0, zeros before 0
        k = jnp.concatenate([jnp.zeros((window, hk, d), F32), k])
        v = jnp.concatenate([jnp.zeros((window, hk, d), F32), v])

    def one(i0):
        ub = lax.dynamic_slice_in_dim(u, i0, qb)
        tb = i0 + jnp.arange(qb)
        q = rms_norm((ub @ p["q_proj"]).reshape(qb, hq, d), p["q_norm"], eps)
        q = rope(q, tb, inv, factor, dim).reshape(qb, hk, g, d)
        if window:
            kb = lax.dynamic_slice_in_dim(k, i0 + window - span + qb, span)
            vb = lax.dynamic_slice_in_dim(v, i0 + window - span + qb, span)
            at = i0 + qb - span + jnp.arange(span)
        else:
            kb, vb, at = k, v, pos
        s = jnp.einsum("thgd,shd->hgts", q, kb) * (d ** -0.5)
        seen = (tb[:, None] >= at[None, :]) & (at[None, :] >= 0)
        if window:
            seen &= tb[:, None] - at[None, :] < window
        pr = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        a = jnp.einsum("hgts,shd->thgd", pr, vb).reshape(qb, hq, d)
        gate = jax.nn.sigmoid(ub @ p["gate"])                  # [qb, hq]
        return (a * gate[..., None]).reshape(qb, hq * d) @ p["o_proj"]

    out = lax.map(one, jnp.arange(0, t, qb))
    return (h + out.reshape(t, -1))[:n]


def swiglu(x, gate, up, down):
    """(silu(x gate) * x up) down, a block of tokens at a time."""
    t = x.shape[0]
    b = math.gcd(t, TOKEN_BLOCK)
    one = lambda xb: (jax.nn.silu(xb @ gate.astype(F32))
                      * (xb @ up.astype(F32))) @ down.astype(F32)
    return lax.map(one, x.reshape(t // b, b, -1)).reshape(t, -1)


def route(m, router, k: int, scaling: float):
    """Router over ALL experts: ``(chosen [T, k], weights [T, k], logits
    [T, E])``, the weights softmax over the chosen logits times
    ``scaling``."""
    r = m @ router
    top, chosen = lax.top_k(r, k)
    return chosen, scaling * jax.nn.softmax(top, axis=-1), r


def routed_experts(m, chosen, w, e_gate, e_up, e_down, first: int,
                   layer=None):
    """``sum over chosen & held`` of ``w_e SwiGLU_e(m)``: the held stacks
    ``e_* [n, ...]`` (or, given ``layer``, the stacks of all layers ``[L, n,
    ...]``, read at that layer one expert at a time, never copied whole)
    are experts ``first .. first + n``; each multiplies only the rows that
    chose it, ``TOKEN_BLOCK`` of them at a time."""
    t, n = m.shape[0], e_gate.shape[-3]
    b = min(TOKEN_BLOCK, t)
    pad = -(-t // b) * b

    def expert(j, acc):
        hit = chosen == first + j                               # [T, k]
        wt = jnp.sum(jnp.where(hit, w, 0.0), axis=-1)           # [T]
        took = jnp.any(hit, axis=-1)
        rows = jnp.nonzero(took, size=pad, fill_value=0)[0]
        count = jnp.sum(took)
        g, u, d = ((x[j] if layer is None else x[layer, j]).astype(F32)
                   for x in (e_gate, e_up, e_down))

        def piece(c, acc):
            r = lax.dynamic_slice_in_dim(rows, c * b, b)
            live = c * b + jnp.arange(b) < count
            x = m[r]
            y = (jax.nn.silu(x @ g) * (x @ u)) @ d
            return acc.at[r].add(jnp.where(live, wt[r], 0.0)[:, None] * y)

        return lax.fori_loop(0, (count + b - 1) // b, piece, acc)

    return lax.fori_loop(0, n, expert, jnp.zeros_like(m))


def held_margin(r, k: int, first: int, n: int):
    """Per token, the least distance of a HELD expert's logit from the
    selection boundary (midway between the k-th and (k+1)-th logit), over
    the root-mean-square of the token's logits: under ``TIE``, round-off
    may decide whether that expert is chosen."""
    top = lax.top_k(r, k + 1)[0]
    boundary = 0.5 * (top[:, k - 1] + top[:, k])
    held = r[:, first:first + n] - boundary[:, None]
    return jnp.min(jnp.abs(held), axis=-1) / jnp.sqrt(jnp.mean(r * r, -1))


def dims_of(cfg: Dict[str, Any], kind: str):
    z = sizes(cfg)
    inv, factor, dim = rope_table(cfg, kind)
    return (z["Hq_" + kind], z["Hk"], z["D"], float(cfg["rms_norm_eps"]),
            (tuple(float(f) for f in inv), factor, dim))


def layer_params(flat, prefix: str, names, l: int):
    """Layer ``l`` of a stack in float32, the expert stacks left out: a
    routed layer reads them whole and bfloat16, one expert at a time where
    it is used (a layer's slice would be a 2.3 GB copy at these widths)."""
    return {n: flat[prefix + n][l].astype(F32) for n in names
            if not n.startswith("e_")}


@functools.partial(jax.jit, static_argnames=("dims", "window"))
@highest
def dense_layer(p, h, dims, window: int):
    h = attention_block(p, h, dims, window)
    m = rms_norm(h, p["post_norm"], dims[3])
    return h + swiglu(m, p["mlp_gate"], p["mlp_up"], p["mlp_down"])


@functools.partial(jax.jit, static_argnames=(
    "dims", "window", "k", "scaling", "first"))
@highest
def routed_layer(p, experts, l, h, dims, window: int, k: int, scaling: float,
                 first: int):
    """One routed layer, ``l`` of the expert stacks ``experts`` (``e_gate``,
    ``e_up``, ``e_down`` of all layers of its kind): ``(h', margin [T])``."""
    h = attention_block(p, h, dims, window)
    m = rms_norm(h, p["post_norm"], dims[3])
    chosen, w, r = route(m, p["router"], k, scaling)
    y = routed_experts(m, chosen, w, experts["e_gate"], experts["e_up"],
                       experts["e_down"], first, layer=l)
    shared = swiglu(m, p["s_gate"], p["s_up"], p["s_down"])
    return (h + y + shared,
            held_margin(r, k, first, experts["e_gate"].shape[1]))


def hidden_states(flat, cfg, ids):
    """ids [T] -> ``(final-normed hidden [T, H], margin [T])``: one full
    forward, layer by layer; the margin is the least over the routed
    layers."""
    z = sizes(cfg)
    kinds = layer_kinds(cfg)
    h = flat["embed"][ids].astype(F32)
    margin = jnp.full(ids.shape, jnp.inf, F32)
    seen = {FULL: 0, WINDOW: 0}
    scaling = float(cfg["moe_routed_scaling_factor"])
    for l, kind in enumerate(kinds):
        window = z["W"] if kind == WINDOW else 0
        if l < z["kd"]:
            h = dense_layer(layer_params(flat, "d.", DENSE, l), h,
                            dims_of(cfg, kind), window)
            continue
        pre = PREFIX[kind]
        p = layer_params(flat, pre, ROUTED, seen[kind])
        experts = {n: flat[pre + n] for n in ("e_gate", "e_up", "e_down")}
        h, here = routed_layer(p, experts, jnp.int32(seen[kind]), h,
                               dims_of(cfg, kind), window, z["k"], scaling,
                               z["first"])
        seen[kind] += 1
        margin = jnp.minimum(margin, here)
    return _final_norm(flat["final_norm"], h, float(cfg["rms_norm_eps"])), \
        margin


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(w, h, eps):
    return rms_norm(h, w.astype(F32), eps)


@jax.jit
@highest
def logits_of(flat, hidden):
    return hidden @ flat["lm_head"].astype(F32)


@jax.jit
@highest
def _gaps(lm_head, hidden, served):
    """For each position: the reference's best logit minus the logit of the
    token that was served after it."""
    logits = hidden @ lm_head.astype(F32)
    picked = jnp.take_along_axis(logits, served[:, None], axis=1)[:, 0]
    return jnp.max(logits, axis=-1) - picked


def gaps_by_the_rule(gaps: np.ndarray, tied: np.ndarray):
    """The untied tokens' gaps as they are; each tied token's replaced by
    the ``TIED_QUANTILE`` of the tied tokens' or, where more than
    ``FLIP_SHARE`` of them read over ``FLIP_GAP``, by their widest (0 where
    they are fewer than ``MIN_TIED``).  Returns the gaps, what the tied
    tokens carry and the share of them over ``FLIP_GAP``."""
    flipped = float(np.mean(gaps[tied] > FLIP_GAP)) if tied.any() else 0.0
    if tied.sum() < MIN_TIED:
        return np.where(tied, 0.0, gaps), 0.0, flipped
    among = float(np.quantile(gaps[tied], TIED_QUANTILE)
                  if flipped <= FLIP_SHARE else np.max(gaps[tied]))
    return np.where(tied, among, gaps), among, flipped


def served_token_gaps(flat, cfg, prompt: Sequence[int],
                      served: Sequence[int], pad_to: int = 4096,
                      rows: int = 2048) -> np.ndarray:
    """The gap of every served token of one request, teacher-forced through
    ONE full forward over prompt + served tokens; the tied tokens (module
    docstring) each carry what :func:`gaps_by_the_rule` gives them.  The
    sequence is padded on the right (causal, so the pad changes nothing) to
    a multiple of ``pad_to`` so that few programs are compiled; the logits
    exist ``rows`` positions at a time."""
    seq = list(prompt) + list(served)
    n, t = len(seq), -(-len(seq) // pad_to) * pad_to
    ids = np.zeros((t,), np.int32)
    ids[:n] = seq
    hidden, margin = hidden_states(flat, cfg, jnp.asarray(ids))
    first = len(prompt) - 1                 # position that predicts served[0]
    m = -(-len(served) // rows) * rows
    at = np.minimum(np.arange(first, first + m), t - 1)
    tok = np.zeros((m,), np.int32)
    tok[:len(served)] = served
    gaps = np.concatenate([np.asarray(_gaps(
        flat["lm_head"], hidden[jnp.asarray(at[i:i + rows])],
        jnp.asarray(tok[i:i + rows]))) for i in range(0, m, rows)])
    gaps = gaps[:len(served)]
    margin = np.asarray(margin)[at[:len(served)]]
    # ``assumed.tie`` (a toy's file only): a toy request serves a dozen
    # tokens, too few for the percentile, so the cell's band would leave
    # nearly all of them out of the comparison
    tie = (cfg.get("assumed") or {}).get("tie", TIE)
    tied = margin < tie
    out, among, flipped = gaps_by_the_rule(gaps, tied)
    rule = ("fewer than MIN_TIED: left out" if tied.sum() < MIN_TIED else
            "their widest" if flipped > FLIP_SHARE else
            f"their {TIED_QUANTILE:.0%} quantile")
    print(f"[bench] reference laguna: of {len(served)} served tokens "
          f"{int((~tied).sum())} have no tie (widest gap "
          f"{float(np.max(gaps, where=~tied, initial=0.0)):.4f}) and "
          f"{int(tied.sum())} a held expert's logit within {tie:g} of the "
          f"rms from the boundary ({flipped:.1%} of them over {FLIP_GAP:g}; "
          f"they carry {among:.4f}, {rule}; widest "
          f"{float(np.max(gaps, where=tied, initial=0.0)):.4f}); gaps over "
          "0.02 by margin under 1e-3, 3e-3, 1e-2, 3e-2, any: "
          + ", ".join(f"{int(((gaps > 0.02) & (margin < c)).sum())}/"
                      f"{int((margin < c).sum())}"
                      for c in (1e-3, 3e-3, 1e-2, 3e-2, np.inf)), flush=True)
    return out
