"""The family ``kimi_k2`` (a configuration's ``model_type`` finds this file):
Kimi-K2's language model — DeepSeek-V3's block at other sizes — as ONE
expert-parallel share of a layer: which weights the share holds and where
each sits in the program's parameter tree, the work one step requires of
it, and its plain reference.  Serving only (training does not fit one chip
at these widths: PERF.md section 4).

The plain reference: float32, ``highest`` matmul precision, ``jax.numpy``
and ``jax.lax`` only, no cache, no absorbed form, no kernels.  It imports
nothing of the program.  The equations, per token ``x`` of a pre-norm block
``h1 = h + Attn(RMSNorm(h))``, ``h2 = h1 + FFN(RMSNorm(h1))`` (eps as the
file states, no biases):

*Latent attention, head i of Hq.*  ``c_q = RMSNorm(x W_qa)``; ``[q_nope_i |
q_rope_i] = c_q W_qb``; ``[c_kv | k_rope] = x W_kva``, ``c_kv <-
RMSNorm(c_kv)``; ``[k_nope_i | v_i] = c_kv W_kvb``.  RoPE on ``q_rope_i``
and on the ONE ``k_rope`` all heads share, INTERLEAVED (channels 2j and
2j+1 are a pair), with YaRN frequencies: below ``low`` =
floor(correction(beta_fast)) the published ``theta^(-2j/d)``, above
``high`` = ceil(correction(beta_slow)) the same over ``factor``, a linear
ramp between; ``mscale == mscale_all_dim`` so cos/sin are unscaled and the
softmax scale carries ``m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) +
1``.  ``score_ij = (q_nope_i . k_nope_ij + q_rope_i . k_rope_j) *
(d_nope + d_rope)^-1/2 * m^2``; causal softmax; ``o = concat_i(sum_j p_ij
v_ij) W_o``.  Computed exactly so, EXPANDED, in query blocks.

*Routed experts.*  ``s = sigmoid(x W_g)`` (all ``published.n_routed_experts``
wide); chosen = top-k of ``s + b`` (``b`` = 0 at a seeded start; ``n_group``
= ``topk_group`` = 1: no group limit); ``w_e = routed_scaling_factor * s_e /
sum over the k chosen of s``; ``y = sum over chosen e of w_e SwiGLU_e(x) +
SwiGLU_shared(x)``.  **The held share:** this configuration holds experts
``deployment.held_experts_first .. + n_routed_experts`` of the published
count; the sum runs over ``chosen & held`` only, the normalisation still
over all the chosen, and that partial result goes on to the next layer (on
one chip the layer runs without its exchange).  ``held`` of
:func:`expert_layer_ffn` is what the share test varies.

*What a served token can show, and what it cannot.*  Top-k is a step
function: where a HELD expert's score lies within round-off of the
selection boundary (midway between the k-th and the (k+1)-th score), the
bfloat16 program and this float32 reference may choose differently, and one
expert more or less moves a logit by 0.2-1 (measured on the chip, PR 29:
the score's error is ~5e-4 as a rule, but a run compares 4e5 scores (token,
layer, held expert), so the band has to hold that many draws' tail:
``TIE`` = 3e-3.  Four served tokens in nine have such a tie in some layer;
1-3 % of THOSE read 0.1-1.4 in sound runs, the program having chosen the
other way, and the rest as the untied do, at most 0.11; the int8 control
reads over 0.1 on a quarter of the tokens of EITHER kind).  Which way a tie
falls is round-off's to decide and says nothing of the program, so a max
over the tied tokens measures the ties, not the program; what a fault
moves is their bulk.  :func:`served_token_gaps` therefore compares every
untied token in full and the tied tokens of a request through ONE number,
the 95th percentile of their gaps, which stands in for each of them (a
request with fewer than ``MIN_TIED`` of them has no percentile but their
max: there they are left out), and says how many there were and the widest
gap among them.

*Dense layers* (the first ``first_k_dense_replace``): SwiGLU of
``intermediate_size``.  *Head:* RMSNorm, untied ``lm_head`` over the held
slice of the vocabulary.
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

F32 = jnp.float32

ATTN = ("input_norm", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b",
        "o", "post_norm")
DENSE = ATTN + ("gate", "up", "down")
MOE = ATTN + ("router", "e_gate", "e_up", "e_down", "s_gate", "s_up",
              "s_down")


# ---------------------------------------------------------------------------
# The family's weights, its layout in the program, its required work
# ---------------------------------------------------------------------------
def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    pub = cfg.get("published") or {}
    kd = cfg["first_k_dense_replace"]
    return dict(
        H=cfg["hidden_size"], Hq=cfg["num_attention_heads"],
        qr=cfg["q_lora_rank"], R=cfg["kv_lora_rank"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], I=cfg["intermediate_size"],
        Im=cfg["moe_intermediate_size"],
        Is=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        held=cfg["n_routed_experts"],
        first=(cfg.get("deployment") or {}).get("held_experts_first", 0),
        E=pub.get("n_routed_experts", cfg["n_routed_experts"]),
        k=cfg["num_experts_per_tok"], V=cfg["vocab_size"],
        kd=kd, n_moe=cfg["num_hidden_layers"] - kd)


def leaf_shapes(cfg: Dict[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, "matrix"|"norm"), in a fixed order.  ``d.*`` leaves
    stack the dense layers, ``m.*`` the expert layers, on their first axis."""
    z = sizes(cfg)
    H, Hq = z["H"], z["Hq"]

    def attn(n):
        return {
            "input_norm": ((n, H), "norm"),
            "q_a": ((n, H, z["qr"]), "matrix"),
            "q_a_norm": ((n, z["qr"]), "norm"),
            "q_b": ((n, z["qr"], Hq * (z["dn"] + z["dr"])), "matrix"),
            "kv_a": ((n, H, z["R"] + z["dr"]), "matrix"),
            "kv_a_norm": ((n, z["R"]), "norm"),
            "kv_b": ((n, z["R"], Hq * (z["dn"] + z["dv"])), "matrix"),
            "o": ((n, Hq * z["dv"], H), "matrix"),
            "post_norm": ((n, H), "norm"),
        }

    out = {"embed": ((z["V"], H), "matrix")}
    kd, nm = z["kd"], z["n_moe"]
    if kd:
        out.update({"d." + k: v for k, v in attn(kd).items()})
        out.update({"d.gate": ((kd, H, z["I"]), "matrix"),
                    "d.up": ((kd, H, z["I"]), "matrix"),
                    "d.down": ((kd, z["I"], H), "matrix")})
    if nm:
        out.update({"m." + k: v for k, v in attn(nm).items()})
        out.update({
            "m.router": ((nm, H, z["E"]), "matrix"),
            "m.e_gate": ((nm, z["held"], H, z["Im"]), "matrix"),
            "m.e_up": ((nm, z["held"], H, z["Im"]), "matrix"),
            "m.e_down": ((nm, z["held"], z["Im"], H), "matrix"),
            "m.s_gate": ((nm, H, z["Is"]), "matrix"),
            "m.s_up": ((nm, H, z["Is"]), "matrix"),
            "m.s_down": ((nm, z["Is"], H), "matrix"),
        })
    out["final_norm"] = ((H,), "norm")
    out["lm_head"] = ((H, z["V"]), "matrix")
    return out


MAKE_PIECE = 1 << 26    # elements drawn at a time (256 MB of float32)


def make(cfg: Dict[str, Any], words) -> Dict[str, Any]:
    """The flat dict of this configuration's weights, as ``weights.make``
    draws them (normal(0, 0.02) matrices, 1 + 0.1 normal norm weights,
    rounded to bfloat16) but PIECE BY PIECE: the largest leaf here is a
    gigabyte of elements, and drawn whole its float32 temporaries alone
    would pass the chip's memory beside the results.  A leaf over
    ``MAKE_PIECE`` elements is drawn slice by slice of its leading axes in
    a loop, each slice under a key of its own.  Traceable, one program."""
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
        n = math.prod(shape[:lead])
        pieces = lax.map(
            lambda j: draw(jax.random.fold_in(k, j), shape[lead:], kind),
            jnp.arange(n, dtype=jnp.uint32))
        out[name] = pieces.reshape(shape)
    return out


def to_program_tree(flat: Dict[str, Any]) -> Dict[str, Any]:
    """The flat dict in the layout of ``automodel_tpu.models.deepseek_v3``
    (two layer stacks, ``[in, out]`` kernels, ``[held, ...]`` expert
    stacks) — the one place the benchmark names the program's tree.  The
    selection bias is no weight of the draw: zero, float32, as at a seeded
    start."""
    kernel = lambda n: {"kernel": flat[n]}
    weight = lambda n: {"weight": flat[n]}

    def block(p):
        return {
            "input_layernorm": weight(p + "input_norm"),
            "post_attention_layernorm": weight(p + "post_norm"),
            "self_attn": {
                "q_a_proj": kernel(p + "q_a"),
                "q_a_layernorm": weight(p + "q_a_norm"),
                "q_b_proj": kernel(p + "q_b"),
                "kv_a_proj_with_mqa": kernel(p + "kv_a"),
                "kv_a_layernorm": weight(p + "kv_a_norm"),
                "kv_b_proj": kernel(p + "kv_b"),
                "o_proj": kernel(p + "o"),
            },
        }

    tree = {"embed_tokens": {"embedding": flat["embed"]},
            "norm": weight("final_norm"), "lm_head": kernel("lm_head")}
    if "d.gate" in flat:
        tree["dense_layers"] = dict(block("d."), mlp={
            "gate_proj": kernel("d.gate"), "up_proj": kernel("d.up"),
            "down_proj": kernel("d.down")})
    if "m.router" in flat:
        router = flat["m.router"]
        tree["layers"] = dict(block("m."), mlp={
            "gate": {"kernel": router,
                     "e_score_correction_bias": jnp.zeros(
                         (router.shape[0], router.shape[2]), F32)},
            "experts": {"gate_proj": kernel("m.e_gate"),
                        "up_proj": kernel("m.e_up"),
                        "down_proj": kernel("m.e_down")},
            "shared_experts": {"gate_proj": kernel("m.s_gate"),
                               "up_proj": kernel("m.s_up"),
                               "down_proj": kernel("m.s_down")}})
    return tree


def from_program_tree(tree: Dict[str, Any]) -> Dict[str, Any]:
    """Inverse of :func:`to_program_tree` (the selection bias left out)."""
    def block(t, p):
        a = t["self_attn"]
        return {
            p + "input_norm": t["input_layernorm"]["weight"],
            p + "q_a": a["q_a_proj"]["kernel"],
            p + "q_a_norm": a["q_a_layernorm"]["weight"],
            p + "q_b": a["q_b_proj"]["kernel"],
            p + "kv_a": a["kv_a_proj_with_mqa"]["kernel"],
            p + "kv_a_norm": a["kv_a_layernorm"]["weight"],
            p + "kv_b": a["kv_b_proj"]["kernel"],
            p + "o": a["o_proj"]["kernel"],
            p + "post_norm": t["post_attention_layernorm"]["weight"],
        }

    out = {"embed": tree["embed_tokens"]["embedding"]}
    if "dense_layers" in tree:
        d = tree["dense_layers"]
        out.update(block(d, "d."))
        out.update({"d.gate": d["mlp"]["gate_proj"]["kernel"],
                    "d.up": d["mlp"]["up_proj"]["kernel"],
                    "d.down": d["mlp"]["down_proj"]["kernel"]})
    if "layers" in tree:
        m = tree["layers"]
        out.update(block(m, "m."))
        mlp = m["mlp"]
        out.update({
            "m.router": mlp["gate"]["kernel"],
            "m.e_gate": mlp["experts"]["gate_proj"]["kernel"],
            "m.e_up": mlp["experts"]["up_proj"]["kernel"],
            "m.e_down": mlp["experts"]["down_proj"]["kernel"],
            "m.s_gate": mlp["shared_experts"]["gate_proj"]["kernel"],
            "m.s_up": mlp["shared_experts"]["up_proj"]["kernel"],
            "m.s_down": mlp["shared_experts"]["down_proj"]["kernel"]})
    out["final_norm"] = tree["norm"]["weight"]
    out["lm_head"] = tree["lm_head"]["kernel"]
    return out


def model_config(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The keys of a configuration file that ``build_model`` takes: the
    router at the PUBLISHED expert count, and beside it the share this
    configuration holds (the file's own ``n_routed_experts``)."""
    keys = ("model_type", "vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "num_experts_per_tok", "n_shared_experts",
            "n_group", "topk_group", "norm_topk_prob",
            "routed_scaling_factor", "first_k_dense_replace", "rope_theta",
            "rope_scaling", "max_position_embeddings", "rms_norm_eps",
            "tie_word_embeddings", "attention_bias", "torch_dtype")
    out = {k: cfg[k] for k in keys if k in cfg}
    z = sizes(cfg)
    out["n_routed_experts"] = z["E"]
    if z["held"] != z["E"]:
        out["held_experts"] = [z["first"], z["held"]]
    out["rope_interleave"] = True
    out["moe_capacity_factor"] = None       # serving drops nothing
    return out


def matmul_params(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameters that sit in a matrix product, per position, summed over
    the layers, and in the head.  An expert layer counts its attention, the
    router, the shared expert and the share of the k chosen experts that
    this configuration holds on average (k * held / published); a dense
    layer its attention and its MLP."""
    z = sizes(cfg)
    H, Hq = z["H"], z["Hq"]
    attn = (H * z["qr"] + z["qr"] * Hq * (z["dn"] + z["dr"])
            + H * (z["R"] + z["dr"]) + z["R"] * Hq * (z["dn"] + z["dv"])
            + Hq * z["dv"] * H)
    expert = 3 * H * z["Im"]
    moe = (attn + H * z["E"] + 3 * H * z["Is"]
           + z["k"] * z["held"] * expert // z["E"])
    dense = attn + 3 * H * z["I"]
    layers = z["kd"] * dense + z["n_moe"] * moe
    return {"layer": layers // max(1, z["kd"] + z["n_moe"]),
            "layers": layers, "head": H * z["V"], "expert": expert}


def attention_pair_flops(cfg: Dict[str, Any]) -> int:
    """Forward FLOPs per (query, key) pair over all layers, as the latent
    cache requires them (absorbed): every head's score against the
    ``R + d_rope`` wide row, and its value over the row's ``R``."""
    z = sizes(cfg)
    return (2 * z["Hq"] * (z["R"] + z["dr"] + z["R"])
            * cfg["num_hidden_layers"])


def latent_bytes_per_token(cfg: Dict[str, Any], dtype_bytes: int = 2) -> int:
    z = sizes(cfg)
    return (z["R"] + z["dr"]) * dtype_bytes * cfg["num_hidden_layers"]


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------
def highest(fn):
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def yarn_inv_freq(d: int, theta: float, scaling) -> np.ndarray:
    """[d/2] rotation frequencies."""
    base = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    if not scaling:
        return base
    factor = float(scaling["factor"])
    old = float(scaling["original_max_position_embeddings"])

    def correction(rotations):
        return d * math.log(old / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction(scaling.get("beta_slow", 1))), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return base / factor * ramp + base * (1 - ramp)


def softmax_scale(cfg) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg.get("rope_scaling") or {}
    if rs.get("mscale_all_dim") and rs.get("factor", 1) > 1:
        m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
        scale *= m * m
    return scale


def rope_interleaved(x, pos, inv_freq):
    """x [T, ..., d]: channels (2j, 2j+1) rotate by pos * inv_freq[j]."""
    ang = pos.astype(F32)[:, None] * jnp.asarray(inv_freq, F32)[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[-1],)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


TIE = 3e-3          # a held expert's score this near the boundary: a tie
HEAD_GROUP = 16     # heads whose scores are live at a time
TOKEN_BLOCK = 256   # tokens whose MLP intermediates are live at a time


def attention(p, x, dims, q_block: int):
    """Expanded latent attention over one sequence x [T, H] -> [T, H]: the
    equations of the module docstring, a group of heads and a block of
    queries at a time so that a 16k-token sequence fits beside the weights
    (the grouping changes which numbers are live, not what is computed)."""
    (hq, dn, dr, dv, r, eps, inv_freq, scale) = dims
    t = x.shape[0]
    hg = math.gcd(hq, HEAD_GROUP)
    pos = jnp.arange(t, dtype=jnp.int32)
    c_q = rms_norm(x @ p["q_a"], p["q_a_norm"], eps)
    ckv = x @ p["kv_a"]
    c_kv = rms_norm(ckv[:, :r], p["kv_a_norm"], eps)
    k_rope = rope_interleaved(ckv[:, r:], pos, inv_freq)
    qb = min(q_block, t)
    by_group = lambda w, d: jnp.moveaxis(
        w.reshape(w.shape[0], hq // hg, hg * d), 1, 0)
    w_qb, w_kvb = by_group(p["q_b"], dn + dr), by_group(p["kv_b"], dn + dv)
    w_o = p["o"].reshape(hq // hg, hg * dv, p["o"].shape[1])

    def group(g, acc):
        q = (c_q @ w_qb[g]).reshape(t, hg, dn + dr)
        kv = (c_kv @ w_kvb[g]).reshape(t, hg, dn + dv)
        q_nope, q_rope = q[..., :dn], rope_interleaved(q[..., dn:], pos,
                                                       inv_freq)
        k_nope, v = kv[..., :dn], kv[..., dn:]

        def block(i):
            qn = lax.dynamic_slice_in_dim(q_nope, i * qb, qb)
            qr = lax.dynamic_slice_in_dim(q_rope, i * qb, qb)
            s = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
                 + jnp.einsum("qhd,kd->hqk", qr, k_rope)) * scale
            mask = (i * qb + jnp.arange(qb))[:, None] >= pos[None, :]
            pr = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
            return jnp.einsum("hqk,khd->qhd", pr, v).reshape(qb, hg * dv)

        out = lax.map(block, jnp.arange(t // qb)).reshape(t, hg * dv)
        return acc + out @ w_o[g]

    return lax.fori_loop(0, hq // hg, group, jnp.zeros_like(x))


def swiglu(x, gate, up, down):
    """(silu(x gate) * x up) down, a block of tokens at a time."""
    t = x.shape[0]
    b = math.gcd(t, TOKEN_BLOCK)
    one = lambda xb: (jax.nn.silu(xb @ gate) * (xb @ up)) @ down
    return lax.map(one, x.reshape(t // b, b, -1)).reshape(t, -1)


def boundary_offsets(s, k: int, first: int, n: int):
    """``[T, n]``: each HELD expert's score (``s[:, first:first + n]``)
    minus the selection boundary, midway between the k-th and the (k+1)-th
    largest score.  Within :data:`TIE` of nought, round-off decides whether
    the expert is chosen."""
    top = lax.top_k(s, k + 1)[0]
    boundary = 0.5 * (top[:, k - 1] + top[:, k])
    return s[:, first:first + n] - boundary[:, None]


def expert_layer_ffn(p, x, k: int, scaling: float, first: int,
                     held: Sequence[int] = None, with_ties: bool = False):
    """Router over ALL experts, then the routed sum over the experts of
    ``p["e_*"]`` (global ids ``first + j``; ``held``: which j take part,
    default all) and the shared expert, apart: ``(routed, shared)`` and,
    ``with_ties``, per token how many held experts lie within ``TIE`` of
    the boundary (:func:`boundary_offsets`).  x [T, H]."""
    s = jax.nn.sigmoid(x @ p["router"])                       # [T, E]
    top_s, top_i = lax.top_k(s, k)          # bias 0: selection on s itself
    w = scaling * top_s / jnp.sum(top_s, axis=-1, keepdims=True)
    n = p["e_gate"].shape[0]
    take = jnp.zeros((n,), bool).at[jnp.asarray(
        list(range(n)) if held is None else list(held), jnp.int32)].set(True)

    def one(j, acc):
        # the weight token by token of expert first + j, 0 where not chosen
        wj = jnp.sum(jnp.where(top_i == first + j, w, 0.0), axis=-1)
        y = swiglu(x, p["e_gate"][j].astype(F32), p["e_up"][j].astype(F32),
                   p["e_down"][j].astype(F32))
        return acc + jnp.where(take[j], wj, 0.0)[:, None] * y

    routed = lax.fori_loop(0, n, one, jnp.zeros_like(x))
    shared = swiglu(x, p["s_gate"], p["s_up"], p["s_down"])
    if with_ties:
        near = jnp.abs(boundary_offsets(s, k, first, n)) < TIE
        return routed, shared, jnp.sum(near, axis=-1)
    return routed, shared


def dims_of(cfg):
    z = sizes(cfg)
    inv = yarn_inv_freq(z["dr"], float(cfg["rope_theta"]),
                        cfg.get("rope_scaling"))
    return (z["Hq"], z["dn"], z["dr"], z["dv"], z["R"],
            float(cfg["rms_norm_eps"]), tuple(float(f) for f in inv),
            softmax_scale(cfg))


def _layer_params(flat, prefix, names, l):
    """Layer ``l`` of a stack in float32; the expert stacks stay bfloat16
    and are cast one expert at a time where they are used."""
    return {n: (flat[prefix + n][l] if n.startswith("e_")
                else flat[prefix + n][l].astype(F32)) for n in names}


@functools.partial(jax.jit, static_argnames=("dims", "q_block"))
@highest
def _dense_layer(p, h, dims, q_block):
    eps = dims[5]
    h = h + attention(p, rms_norm(h, p["input_norm"], eps), dims, q_block)
    x = rms_norm(h, p["post_norm"], eps)
    return h + swiglu(x, p["gate"], p["up"], p["down"])


@functools.partial(jax.jit, static_argnames=(
    "dims", "q_block", "k", "scaling", "first"))
@highest
def _moe_layer(p, h, dims, q_block, k, scaling, first):
    eps = dims[5]
    h = h + attention(p, rms_norm(h, p["input_norm"], eps), dims, q_block)
    x = rms_norm(h, p["post_norm"], eps)
    routed, shared, ties = expert_layer_ffn(p, x, k, scaling, first,
                                            with_ties=True)
    return h + routed + shared, ties


@jax.jit
@highest
def _gaps(final_norm, lm_head, hidden, served, eps):
    """For each position: the reference's best logit minus the logit of the
    token that was served after it."""
    logits = rms_norm(hidden, final_norm.astype(F32), eps) \
        @ lm_head.astype(F32)
    picked = jnp.take_along_axis(logits, served[:, None], axis=1)[:, 0]
    return jnp.max(logits, axis=-1) - picked


def hidden_states(flat, cfg, ids, q_block: int = 256,
                  with_ties: bool = False):
    """ids [T] -> the last layer's output [T, H] (before the final norm),
    layer by layer, one layer's weights in float32 at a time; and,
    ``with_ties``, per token the ties met over the expert layers (held
    experts within ``TIE`` of the boundary)."""
    z, dims = sizes(cfg), dims_of(cfg)
    h = flat["embed"][ids].astype(F32)
    ties = jnp.zeros(ids.shape, jnp.int32)
    for l in range(z["kd"]):
        h = _dense_layer(_layer_params(flat, "d.", DENSE, l), h, dims,
                         q_block)
    for l in range(z["n_moe"]):
        h, here = _moe_layer(
            _layer_params(flat, "m.", MOE, l), h, dims, q_block, z["k"],
            float(cfg["routed_scaling_factor"]), z["first"])
        ties = ties + here
    return (h, ties) if with_ties else h


TIED_QUANTILE = 0.95    # of a request's tied tokens' gaps: stands for each
MIN_TIED = 40           # fewer: the quantile is their max, and they are left out


def gaps_by_the_rule(gaps: np.ndarray, tied: np.ndarray):
    """The untied tokens' gaps as they are; each tied token's replaced by
    the ``TIED_QUANTILE`` of the tied tokens' (0 where they are fewer than
    ``MIN_TIED``).  Returns the gaps and that quantile."""
    among = (float(np.quantile(gaps[tied], TIED_QUANTILE))
             if tied.sum() >= MIN_TIED else 0.0)
    return np.where(tied, among, gaps), among


def served_token_gaps(flat, cfg, prompt: Sequence[int],
                      served: Sequence[int], pad_to: int = 2048) -> np.ndarray:
    """The gap of every served token of one request, teacher-forced; the
    tied tokens (module docstring) each carry the 95th percentile of
    theirs, or 0 where they are fewer than ``MIN_TIED``.  The sequence is padded on the right (causal, so the pad
    changes nothing) to a multiple of ``pad_to`` so that few programs are
    compiled (a layer's program takes 20 s to compile at these widths, and
    a cell's requests are 4k to 17k tokens long: nine lengths, not sixty)."""
    seq = list(prompt) + list(served)
    n, t = len(seq), -(-len(seq) // pad_to) * pad_to
    ids = np.zeros((t,), np.int32)
    ids[:n] = seq
    hidden, ties = hidden_states(flat, cfg, jnp.asarray(ids), with_ties=True)
    first = len(prompt) - 1                 # position that predicts served[0]
    m = -(-len(served) // pad_to) * pad_to  # same: bound the shapes
    rows = np.minimum(np.arange(first, first + m), t - 1)
    tok = np.zeros((m,), np.int32)
    tok[:len(served)] = served
    gaps = _gaps(flat["final_norm"], flat["lm_head"],
                 hidden[jnp.asarray(rows)], jnp.asarray(tok),
                 float(cfg["rms_norm_eps"]))
    gaps = np.asarray(gaps)[:len(served)]
    tied = np.asarray(ties)[rows[:len(served)]] > 0
    out, among = gaps_by_the_rule(gaps, tied)
    print(f"[bench] kimi_k2 reference: of {len(served)} served tokens "
          f"{int((~tied).sum())} have no tie (widest gap "
          f"{float(np.max(gaps, where=~tied, initial=0.0)):.3f}) and "
          f"{int(tied.sum())} a held expert's score within {TIE:g} of the "
          f"selection boundary (their {TIED_QUANTILE:.0%} quantile "
          f"{among:.3f}, widest "
          f"{float(np.max(gaps, where=tied, initial=0.0)):.3f})", flush=True)
    return out
