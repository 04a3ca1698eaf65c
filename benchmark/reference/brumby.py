"""The family ``brumby`` (a configuration's ``model_type`` finds this file):
Brumby-14B's decoder — Qwen3's block with the softmax attention core
replaced by POWER RETENTION — which weights it has and where each sits in
the program's parameter tree, the work a step requires of it, and its plain
reference.  Serving only (training does not fit one chip at these widths:
PERF.md section 4).

The plain reference: float32 at ``highest`` matmul precision, ``jax.numpy``
and ``jax.lax`` only.  It imports nothing of the program and holds no
state, no chunking and no kernel: it computes the ATTENTION form of the
layer over the whole sequence, a block of queries at a time, while the
program runs the recurrent and the chunked form of the same function.

The equations, per token ``x`` of a pre-norm block ``h1 = h +
Ret(RMSNorm(h))``, ``h2 = h1 + W_down(silu(W_gate x') * W_up x')``, ``x' =
RMSNorm(h1)``, with ``d`` = ``head_dim``, ``Hq`` query heads in ``Hk``
groups, one key/value head a group:

* ``q = W_q x``, ``k = W_k x``, ``v = W_v x``; ``q`` and ``k`` each through
  a per-head RMSNorm (weights of ``d``: Qwen3's ``q_norm``, ``k_norm``),
  then RoPE (``rope_theta``, half-split pairs ``(i, i + d/2)``);
* the gate, per key/value head, float32: ``log g_t = logsigmoid(x W_g +
  b_g)``, ``G_t = sum_{j<=t} log g_j``;
* for ``s <= t``: ``a_ts = exp(G_t - G_s) (q_t . k_s / sqrt d)^p``, ``p`` =
  ``assumed.power_degree`` = 2 (even: every weight is >= 0); ``o_t = sum_s
  a_ts v_s / sum_s a_ts``; the layer's output is ``W_o concat_heads(o_t)``.

Departures from the publication (arXiv:2507.04239 and the model's release
note), each because ``config.json`` does not hold it; the configuration's
file lists them under ``assumed`` with their origins: the degree ``p = 2``;
the gate's form (one logit a key/value head, logsigmoid) and its bias
``b_g`` (zero reproduces a bias-free gate; the seeded weights draw it in [4,
8], see :func:`make`); RoPE and the q/k norms KEPT as in the Qwen3 block the
config's keys descend from; the scale ``1 / sqrt d`` inside the power (it
cancels in the quotient and only keeps the numbers in range); no epsilon in
the denominator (``a_tt > 0`` almost surely and no term is negative).
Parameters are bfloat16 numbers held in float32.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark import weights
from benchmark.weights import head_dim

F32 = jnp.float32
GATE_BIAS = (4.0, 8.0)      # the seeded b_g, uniform: g in 0.98 .. 0.9997
                            # (``assumed.gate_bias`` of a file says otherwise)
Q_BLOCK = 256               # queries whose weights are live at a time
GAP_ROWS = 1024             # positions whose logits are live at a time

LAYER_LEAVES = ("input_norm", "q_proj", "k_proj", "v_proj", "o_proj",
                "g_proj", "g_bias", "q_norm", "k_norm",
                "post_attention_norm", "gate_proj", "up_proj", "down_proj")


# ---------------------------------------------------------------------------
# The family's weights, its layout in the program, its required work
# ---------------------------------------------------------------------------
def leaf_shapes(cfg: Dict[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, "matrix"|"norm"|"gate_bias"), in a fixed order.
    Per-layer leaves carry the layer count as their first axis."""
    L, H, I = (cfg["num_hidden_layers"], cfg["hidden_size"],
               cfg["intermediate_size"])
    V, D = cfg["vocab_size"], head_dim(cfg)
    Hq, Hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {
        "embed": ((V, H), "matrix"),
        "input_norm": ((L, H), "norm"),
        "q_proj": ((L, H, Hq * D), "matrix"),
        "k_proj": ((L, H, Hk * D), "matrix"),
        "v_proj": ((L, H, Hk * D), "matrix"),
        "o_proj": ((L, Hq * D, H), "matrix"),
        "g_proj": ((L, H, Hk), "matrix"),
        "g_bias": ((L, Hk), "gate_bias"),
        "q_norm": ((L, D), "norm"),
        "k_norm": ((L, D), "norm"),
        "post_attention_norm": ((L, H), "norm"),
        "gate_proj": ((L, H, I), "matrix"),
        "up_proj": ((L, H, I), "matrix"),
        "down_proj": ((L, I, H), "matrix"),
        "final_norm": ((H,), "norm"),
        "lm_head": ((H, V), "matrix"),
    }


def make(cfg: Dict[str, Any], words) -> Dict[str, Any]:
    """The flat dict of this configuration's weights: ``weights.make``
    (normal(0, 0.02) matrices, 1 + 0.1 normal norm weights, bfloat16) and
    the gate's bias, uniform in ``assumed.gate_bias`` (``GATE_BIAS``).  At a zero-mean gate a random
    model forgets in two tokens and no comparison could see a fault in the
    carried state; with ``b_g`` in [4, 8] and ``x W_g`` of sigma ~1.4
    around it a head remembers tens to thousands of tokens."""
    shapes = leaf_shapes(cfg)
    flat = weights.make({n: s for n, s in shapes.items()
                         if s[1] != "gate_bias"}, words)
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.key(words[0]), words[1]), 0x6A7E)
    lo, hi = (cfg.get("assumed") or {}).get("gate_bias", GATE_BIAS)
    b = jax.random.uniform(key, shapes["g_bias"][0], F32, lo, hi)
    flat["g_bias"] = lax.reduce_precision(b, 8, 7).astype(jnp.bfloat16)
    return {n: flat[n] for n in shapes}


def to_program_tree(flat: Dict[str, Any]) -> Dict[str, Any]:
    """The flat dict in the layout of ``automodel_tpu.models.brumby``
    (stacked layers, ``[in, out]`` kernels) — the one place the benchmark
    names the program's parameter tree."""
    kernel = lambda n: {"kernel": flat[n]}
    weight = lambda n: {"weight": flat[n]}
    return {
        "embed_tokens": {"embedding": flat["embed"]},
        "layers": {
            "input_layernorm": weight("input_norm"),
            "self_attn": {
                "q_proj": kernel("q_proj"), "k_proj": kernel("k_proj"),
                "v_proj": kernel("v_proj"), "o_proj": kernel("o_proj"),
                "g_proj": {"kernel": flat["g_proj"], "bias": flat["g_bias"]},
                "q_norm": weight("q_norm"), "k_norm": weight("k_norm"),
            },
            "post_attention_layernorm": weight("post_attention_norm"),
            "mlp": {"gate_proj": kernel("gate_proj"),
                    "up_proj": kernel("up_proj"),
                    "down_proj": kernel("down_proj")},
        },
        "norm": weight("final_norm"),
        "lm_head": kernel("lm_head"),
    }


def from_program_tree(tree: Dict[str, Any]) -> Dict[str, Any]:
    """Inverse of :func:`to_program_tree`."""
    lay, att = tree["layers"], tree["layers"]["self_attn"]
    return {
        "embed": tree["embed_tokens"]["embedding"],
        "input_norm": lay["input_layernorm"]["weight"],
        "q_proj": att["q_proj"]["kernel"], "k_proj": att["k_proj"]["kernel"],
        "v_proj": att["v_proj"]["kernel"], "o_proj": att["o_proj"]["kernel"],
        "g_proj": att["g_proj"]["kernel"], "g_bias": att["g_proj"]["bias"],
        "q_norm": att["q_norm"]["weight"], "k_norm": att["k_norm"]["weight"],
        "post_attention_norm": lay["post_attention_layernorm"]["weight"],
        "gate_proj": lay["mlp"]["gate_proj"]["kernel"],
        "up_proj": lay["mlp"]["up_proj"]["kernel"],
        "down_proj": lay["mlp"]["down_proj"]["kernel"],
        "final_norm": tree["norm"]["weight"],
        "lm_head": tree["lm_head"]["kernel"],
    }


def power_degree(cfg: Dict[str, Any]) -> int:
    return int((cfg.get("assumed") or {}).get("power_degree", 2))


def model_config(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The keys of a configuration file that ``build_model`` takes."""
    keys = ("model_type", "vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "rope_theta", "rope_scaling",
            "max_position_embeddings", "rms_norm_eps", "tie_word_embeddings",
            "attention_bias", "sliding_window", "use_sliding_window",
            "max_window_layers", "torch_dtype")
    out = {k: cfg[k] for k in keys if k in cfg}
    out["head_dim"] = head_dim(cfg)
    out["power_degree"] = power_degree(cfg)
    return out


def matmul_params(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameters that sit in a matrix product, per layer and in the head
    (weight matrices only: the gate's 8 biases and the norms are not)."""
    h, i, d = cfg["hidden_size"], cfg["intermediate_size"], head_dim(cfg)
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layer = h * (hq + 2 * hk) * d + hq * d * h + h * hk + 3 * h * i
    return {"layer": layer, "layers": layer * cfg["num_hidden_layers"],
            "head": h * cfg["vocab_size"]}


def attention_pair_flops(cfg: Dict[str, Any]) -> int:
    """0: retention has no per-(query, key) work.  ``rooflines/step.py::
    serve_flops`` can credit attention by pairs only, so ``mfu.serve``
    leaves out what a position costs in the retention core
    (:func:`retention_flops_per_position`: 102 MFLOP a position a layer at
    Brumby-14B's widths, 15 % of a position's matmul FLOPs); the core is
    measured by ``retention_roofline.serve`` instead."""
    return 0


def embedding_dim(cfg: Dict[str, Any]) -> int:
    """The size of the symmetric degree-2 embedding of a head: ``d (d + 1)
    / 2``, as the mathematics needs it whatever layout a program keeps."""
    d = head_dim(cfg)
    return d * (d + 1) // 2


def state_bytes_per_row_layer(cfg: Dict[str, Any]) -> int:
    """One sequence's state in one layer: ``S [D, dv]`` and ``z [D]`` a
    key/value head, float32.  34.08 MB at Brumby-14B's widths."""
    return cfg["num_key_value_heads"] * embedding_dim(cfg) * (
        head_dim(cfg) + 1) * 4


def retention_flops_per_position(cfg: Dict[str, Any]) -> int:
    """One position through one layer's core: every key/value head's state
    update and every query head's read-out, a multiply and an add per entry
    of ``[D, dv + 1]``."""
    return ((cfg["num_attention_heads"] + cfg["num_key_value_heads"])
            * 2 * embedding_dim(cfg) * (head_dim(cfg) + 1))


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


def rope(x, pos, theta):
    """x [T, heads, D], pos [T]: rotate pairs (i, i + D/2)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def retention(q, k, v, log_g, degree: int, block: int = Q_BLOCK):
    """The attention form on one sequence: ``q [T, Hq, d]``, ``k, v [T, Hk,
    d]``, ``log_g [T, Hk]`` -> ``[T, Hq, d]``.  ``T`` is a multiple of
    ``block`` or below it."""
    t, hq, d = q.shape
    hk = k.shape[1]
    block = min(block, t)
    G = jnp.cumsum(log_g, axis=0)                           # [T, Hk]
    qg = q.reshape(t // block, block, hk, hq // hk, d)
    at = jnp.arange(t)

    def one(args):
        qb, Gb, tb = args                   # [block, Hk, G, d], [block, Hk]
        s = jnp.einsum("thgd,shd->hgts", qb, k) * (d ** -0.5)
        seen = tb[:, None] >= at[None, :]                   # [block, T]
        decay = jnp.exp(jnp.where(
            seen[None], Gb.T[:, :, None] - G.T[:, None, :], -jnp.inf))
        a = s ** degree * decay[:, None]                    # [Hk, G, t, s]
        o = jnp.einsum("hgts,shd->thgd", a, v)
        return o / jnp.sum(a, axis=-1).transpose(2, 0, 1)[..., None]

    out = lax.map(one, (qg, G.reshape(-1, block, hk),
                        at.reshape(-1, block)))
    return out.reshape(t, hq, d)


def layer_row(p, h, pos, dims):
    """One decoder layer on one sequence: h [T, H], pos [T]."""
    hq, hk, d, eps, theta, degree = dims
    t = h.shape[0]
    x = rms_norm(h, p["input_norm"], eps)
    q = rms_norm((x @ p["q_proj"]).reshape(t, hq, d), p["q_norm"], eps)
    k = rms_norm((x @ p["k_proj"]).reshape(t, hk, d), p["k_norm"], eps)
    v = (x @ p["v_proj"]).reshape(t, hk, d)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    log_g = jax.nn.log_sigmoid(x @ p["g_proj"] + p["g_bias"])
    o = retention(q, k, v, log_g, degree).reshape(t, hq * d)
    h = h + o @ p["o_proj"]
    x = rms_norm(h, p["post_attention_norm"], eps)
    return h + (jax.nn.silu(x @ p["gate_proj"])
                * (x @ p["up_proj"])) @ p["down_proj"]


def dims_of(cfg: Dict[str, Any]):
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            head_dim(cfg), float(cfg["rms_norm_eps"]),
            float(cfg["rope_theta"]), power_degree(cfg))


def layer_params(flat, l):
    return {n: flat[n][l].astype(F32) for n in LAYER_LEAVES}


@functools.partial(jax.jit, static_argnames=("dims", "n_layers"))
@highest
def hidden_states(flat, ids, dims, n_layers):
    """ids [T] -> final-normed hidden [T, H]: one full forward."""
    t = ids.shape[0]
    h = flat["embed"][ids].astype(F32)
    pos = jnp.arange(t, dtype=jnp.int32)

    def body(h, l):
        return layer_row(layer_params(flat, l), h, pos, dims), None

    h, _ = lax.scan(body, h, jnp.arange(n_layers))
    return rms_norm(h, flat["final_norm"].astype(F32), dims[3])


@jax.jit
@highest
def logits_of(flat, hidden):
    return hidden @ flat["lm_head"].astype(F32)


@jax.jit
@highest
def _gaps(flat, hidden, served):
    """For each position: the reference's best logit minus the logit of the
    token that was served after it."""
    logits = hidden @ flat["lm_head"].astype(F32)
    picked = jnp.take_along_axis(logits, served[:, None], axis=1)[:, 0]
    return jnp.max(logits, axis=-1) - picked


def served_token_gaps(flat, cfg, prompt: Sequence[int],
                      served: Sequence[int], pad_to: int = 512) -> np.ndarray:
    """The gap of every served token of one request, teacher-forced through
    ONE full forward over prompt + served tokens: padded on the right
    (causal, so the pad changes nothing) to a multiple of ``pad_to`` so
    that few programs are compiled; the logits exist ``GAP_ROWS`` positions
    at a time."""
    seq = list(prompt) + list(served)
    n, t = len(seq), -(-len(seq) // pad_to) * pad_to
    ids = np.zeros((t,), np.int32)
    ids[:n] = seq
    hidden = hidden_states(flat, jnp.asarray(ids), dims_of(cfg),
                           cfg["num_hidden_layers"])
    first = len(prompt) - 1                 # position that predicts served[0]
    m = -(-len(served) // GAP_ROWS) * GAP_ROWS
    rows = np.minimum(np.arange(first, first + m), t - 1)
    tok = np.zeros((m,), np.int32)
    tok[:len(served)] = served
    gaps = [np.asarray(_gaps(flat, hidden[jnp.asarray(rows[i:i + GAP_ROWS])],
                             jnp.asarray(tok[i:i + GAP_ROWS])))
            for i in range(0, m, GAP_ROWS)]
    return np.concatenate(gaps)[:len(served)]
