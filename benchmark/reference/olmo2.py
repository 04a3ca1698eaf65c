"""The family ``olmo2`` (a configuration's ``model_type`` finds this file):
which weights it has and where each sits in the program's parameter tree,
the work one step requires of it, and its plain reference.

The plain reference of OLMo-2: float32, ``jax.numpy`` only, no kernels, no
cache, no batching tricks.  It imports nothing of the program.

It follows ``transformers/models/olmo2/modeling_olmo2.py`` as published:
no input norms; RMSNorm over the whole q and k projection (not per head)
before the head split and RoPE (half-split pairs); the block norms applied
to the attention and MLP OUTPUT before the residual add; SwiGLU MLP; an
untied head.  Departures, each because the configuration states it:
parameters are bfloat16 numbers (held in float32 here, rounded to bfloat16
after every update); everything else is float32 at ``highest`` matmul
precision (on a TPU float32 matmuls otherwise run in bfloat16 passes).

Training follows the cell's optimizer as stated in its recipe: AdamW, loss =
sum of token cross-entropies over the step's label tokens divided by their
count.  To fit one chip beside nothing else it walks the model layer by
layer: the forward keeps each layer's input, the backward takes one layer's
vjp at a time and applies that layer's update on the spot, so only the
first step's gradient (which is Adam's whole state after one step) is held.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark import weights
from benchmark.weights import head_dim

IGNORE = -100
F32 = jnp.float32


# ---------------------------------------------------------------------------
# The family's weights, its layout in the program, its required work
# ---------------------------------------------------------------------------
def leaf_shapes(cfg: Dict[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, "matrix"|"norm"), in a fixed order.  Per-layer leaves
    carry the layer count as their first axis."""
    L, H, I = (cfg["num_hidden_layers"], cfg["hidden_size"],
               cfg["intermediate_size"])
    V, D = cfg["vocab_size"], head_dim(cfg)
    Hq, Hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {
        "embed": ((V, H), "matrix"),
        "q_proj": ((L, H, Hq * D), "matrix"),
        "k_proj": ((L, H, Hk * D), "matrix"),
        "v_proj": ((L, H, Hk * D), "matrix"),
        "o_proj": ((L, Hq * D, H), "matrix"),
        "q_norm": ((L, Hq * D), "norm"),
        "k_norm": ((L, Hk * D), "norm"),
        "post_attention_norm": ((L, H), "norm"),
        "gate_proj": ((L, H, I), "matrix"),
        "up_proj": ((L, H, I), "matrix"),
        "down_proj": ((L, I, H), "matrix"),
        "post_feedforward_norm": ((L, H), "norm"),
        "final_norm": ((H,), "norm"),
        "lm_head": ((H, V), "matrix"),
    }


LAYER_LEAVES = ("q_proj", "k_proj", "v_proj", "o_proj", "q_norm", "k_norm",
                "post_attention_norm", "gate_proj", "up_proj", "down_proj",
                "post_feedforward_norm")


def make(cfg: Dict[str, Any], words) -> Dict[str, Any]:
    """The flat dict of this configuration's weights (``weights.make``)."""
    return weights.make(leaf_shapes(cfg), words)


def to_program_tree(flat: Dict[str, Any]) -> Dict[str, Any]:
    """The flat dict in the layout of ``automodel_tpu.models.olmo2`` (stacked
    layers, ``[in, out]`` kernels) — the one place the benchmark names the
    program's parameter tree."""
    kernel = lambda n: {"kernel": flat[n]}
    weight = lambda n: {"weight": flat[n]}
    return {
        "embed_tokens": {"embedding": flat["embed"]},
        "layers": {
            "self_attn": {
                "q_proj": kernel("q_proj"), "k_proj": kernel("k_proj"),
                "v_proj": kernel("v_proj"), "o_proj": kernel("o_proj"),
                "q_norm": weight("q_norm"), "k_norm": weight("k_norm"),
            },
            "post_attention_layernorm": weight("post_attention_norm"),
            "mlp": {"gate_proj": kernel("gate_proj"),
                    "up_proj": kernel("up_proj"),
                    "down_proj": kernel("down_proj")},
            "post_feedforward_layernorm": weight("post_feedforward_norm"),
        },
        "norm": weight("final_norm"),
        "lm_head": kernel("lm_head"),
    }


def from_program_tree(tree: Dict[str, Any]) -> Dict[str, Any]:
    """Inverse of :func:`to_program_tree` (for reading the program's
    parameters and optimizer moments leaf by leaf)."""
    lay, att = tree["layers"], tree["layers"]["self_attn"]
    return {
        "embed": tree["embed_tokens"]["embedding"],
        "q_proj": att["q_proj"]["kernel"], "k_proj": att["k_proj"]["kernel"],
        "v_proj": att["v_proj"]["kernel"], "o_proj": att["o_proj"]["kernel"],
        "q_norm": att["q_norm"]["weight"], "k_norm": att["k_norm"]["weight"],
        "post_attention_norm": lay["post_attention_layernorm"]["weight"],
        "gate_proj": lay["mlp"]["gate_proj"]["kernel"],
        "up_proj": lay["mlp"]["up_proj"]["kernel"],
        "down_proj": lay["mlp"]["down_proj"]["kernel"],
        "post_feedforward_norm": lay["post_feedforward_layernorm"]["weight"],
        "final_norm": tree["norm"]["weight"],
        "lm_head": tree["lm_head"]["kernel"],
    }


def model_config(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The keys of a configuration file that ``build_model`` takes."""
    keys = ("model_type", "vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "rope_theta", "rope_scaling",
            "max_position_embeddings", "rms_norm_eps", "tie_word_embeddings",
            "attention_bias", "torch_dtype")
    out = {k: cfg[k] for k in keys if k in cfg}
    out["head_dim"] = head_dim(cfg)
    return out


def matmul_params(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameters that sit in a matrix product, per layer and in the head."""
    h, i, d = cfg["hidden_size"], cfg["intermediate_size"], head_dim(cfg)
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layer = h * (hq + 2 * hk) * d + hq * d * h + 3 * h * i
    return {"layer": layer, "layers": layer * cfg["num_hidden_layers"],
            "head": h * cfg["vocab_size"]}


def attention_pair_flops(cfg: Dict[str, Any]) -> int:
    """Forward FLOPs per (query, key) pair over all layers: QK^T and PV."""
    return (4 * cfg["num_attention_heads"] * head_dim(cfg)
            * cfg["num_hidden_layers"])


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
    """x [S, heads, D], pos [S]: rotate pairs (i, i + D/2)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer_row(p, h, pos, seg, dims):
    """One decoder layer on one row: h [S, H], pos/seg [S]."""
    hq, hk, d, eps, theta = dims
    s = h.shape[0]
    q = rms_norm(h @ p["q_proj"], p["q_norm"], eps).reshape(s, hq, d)
    k = rms_norm(h @ p["k_proj"], p["k_norm"], eps).reshape(s, hk, d)
    v = (h @ p["v_proj"]).reshape(s, hk, d)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    if hk != hq:
        k, v = (jnp.repeat(t, hq // hk, axis=1) for t in (k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, k) * (d ** -0.5)
    idx = jnp.arange(s)
    mask = (idx[:, None] >= idx[None, :]) & (seg[:, None] == seg[None, :])
    probs = jax.nn.softmax(jnp.where(mask[None], scores, -1e30), axis=-1)
    attn = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, hq * d)
    h = h + rms_norm(attn @ p["o_proj"], p["post_attention_norm"], eps)
    mlp = (jax.nn.silu(h @ p["gate_proj"]) * (h @ p["up_proj"])) @ p["down_proj"]
    return h + rms_norm(mlp, p["post_feedforward_norm"], eps)


def layer(p, h, pos, seg, dims):
    """h [B, S, H]: rows one after another, so one row's scores are live."""
    return lax.map(lambda a: layer_row(p, a[0], a[1], a[2], dims),
                   (h, pos, seg))


def dims_of(cfg: Dict[str, Any]):
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            head_dim(cfg), float(cfg["rms_norm_eps"]),
            float(cfg["rope_theta"]))


def layer_params(flat, l):
    return {n: flat[n][l].astype(F32) for n in LAYER_LEAVES}


# ---------------------------------------------------------------------------
# Serving: one full forward over a prompt with its served tokens
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("dims", "n_layers"))
@highest
def _hidden(flat, ids, dims, n_layers):
    """ids [T] -> final-normed hidden [T, H]."""
    t = ids.shape[0]
    h = flat["embed"][ids].astype(F32)[None]
    pos = jnp.arange(t, dtype=jnp.int32)[None]
    seg = jnp.ones((1, t), jnp.int32)

    def body(h, l):
        return layer(layer_params(flat, l), h, pos, seg, dims), None

    h, _ = lax.scan(body, h, jnp.arange(n_layers))
    return rms_norm(h[0], flat["final_norm"].astype(F32), dims[3])


@jax.jit
@highest
def _gaps(flat, hidden, served):
    """For each position: the reference's best logit minus the logit of the
    token that was served after it."""
    logits = hidden @ flat["lm_head"].astype(F32)
    picked = jnp.take_along_axis(logits, served[:, None], axis=1)[:, 0]
    return jnp.max(logits, axis=-1) - picked


def served_token_gaps(flat, cfg, prompt: Sequence[int],
                      served: Sequence[int], pad_to: int = 256) -> np.ndarray:
    """The gap of every served token of one request, teacher-forced: the
    sequence is padded on the right (causal, so the pad changes nothing) to
    a multiple of ``pad_to`` so that few programs are compiled."""
    seq = list(prompt) + list(served)
    n, t = len(seq), -(-len(seq) // pad_to) * pad_to
    ids = np.zeros((t,), np.int32)
    ids[:n] = seq
    hidden = _hidden(flat, jnp.asarray(ids), dims_of(cfg),
                     cfg["num_hidden_layers"])
    first = len(prompt) - 1                 # position that predicts served[0]
    m = -(-len(served) // 64) * 64          # same: bound the shapes
    rows = np.minimum(np.arange(first, first + m), t - 1)
    tok = np.zeros((m,), np.int32)
    tok[:len(served)] = served
    gaps = _gaps(flat, hidden[jnp.asarray(rows)], jnp.asarray(tok))
    return np.asarray(gaps)[:len(served)]


# ---------------------------------------------------------------------------
# Training: AdamW steps, layer by layer
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("dims",))
@highest
def _layer_fwd(p, h, pos, seg, dims):
    return layer(p, h, pos, seg, dims)


@functools.partial(jax.jit, static_argnames=("dims",))
@highest
def _layer_bwd(p, h, pos, seg, dh, dims):
    _, vjp = jax.vjp(lambda p_, h_: layer(p_, h_, pos, seg, dims), p, h)
    return vjp(dh)


@functools.partial(jax.jit, static_argnames=("eps", "chunk"))
@highest
def _head(norm_w, head, h, labels, eps, chunk):
    """(sum CE / label count) and its gradients w.r.t. the final norm, the
    head and the hidden states; logits exist ``chunk`` tokens at a time."""
    hs = h.shape[-1]
    x = h.reshape(-1, chunk, hs)
    y = labels.reshape(-1, chunk)
    denom = jnp.maximum(jnp.sum(labels != IGNORE), 1).astype(F32)

    def loss_fn(norm_w, head, x):
        @jax.checkpoint
        def one(xc, yc):
            logits = rms_norm(xc, norm_w, eps) @ head
            lse = jax.nn.logsumexp(logits, axis=-1)
            pick = jnp.take_along_axis(
                logits, jnp.maximum(yc, 0)[:, None], axis=1)[:, 0]
            return jnp.sum(jnp.where(yc != IGNORE, lse - pick, 0.0))

        return jnp.sum(lax.map(lambda a: one(*a), (x, y))) / denom

    loss, (gn, gh, gx) = jax.value_and_grad(loss_fn, (0, 1, 2))(
        norm_w, head, x)
    return loss, gn, gh, gx.reshape(h.shape)


@jax.jit
def _embed_grad(ids, dh, like):
    return jnp.zeros(like.shape, F32).at[ids.reshape(-1)].add(
        dh.reshape(-1, dh.shape[-1]))


def _adam_leaf(p, g, g1, step, opt):
    """One leaf's AdamW update at ``step`` (1 or 2) from this step's
    gradient and, at step 2, the first step's (Adam's state after one step
    is (1-b1) g1 and (1-b2) g1^2).  Returns the new parameter, rounded to
    bfloat16 as the configuration stores it, and |g|^2."""
    lr, b1, b2, eps, wd = opt
    if step == 1:
        mu, nu = (1 - b1) * g, (1 - b2) * g * g
    else:
        mu = b1 * (1 - b1) * g1 + (1 - b1) * g
        nu = b2 * (1 - b2) * g1 * g1 + (1 - b2) * g * g
    upd = (mu / (1 - b1 ** step)) / (jnp.sqrt(nu / (1 - b2 ** step)) + eps)
    p = p.astype(F32)
    return (p - lr * (upd + wd * p)).astype(jnp.bfloat16), jnp.sum(g * g)


@functools.partial(jax.jit, static_argnames=("step", "opt"),
                   donate_argnums=(0,))
def _apply_layer(stacked, l, g, g1, step, opt):
    """Update layer ``l`` of every stacked leaf in place."""
    new, gsq = {}, {}
    for n in g:
        p = lax.dynamic_index_in_dim(stacked[n], l, 0, keepdims=False)
        p, gsq[n] = _adam_leaf(p, g[n], None if g1 is None else g1[n],
                               step, opt)
        new[n] = lax.dynamic_update_index_in_dim(stacked[n], p, l, 0)
    return new, gsq


_apply_leaf = jax.jit(_adam_leaf, static_argnames=("step", "opt"))


@jax.jit
def _take_layer(stacked, l):
    return {n: lax.dynamic_index_in_dim(a, l, 0, keepdims=False).astype(F32)
            for n, a in stacked.items()}


class TrainReference:
    """Follows the program's first two optimizer steps.  ``flat`` is the
    benchmark's bfloat16 weight dict (this object takes it over and updates
    it layer by layer); ``make_start`` makes the same weights again for the
    final comparison, so that no second copy is held meanwhile."""

    def __init__(self, flat: Dict[str, Any], make_start, cfg: Dict[str, Any],
                 opt: Dict[str, Any], head_chunk: int = 1024):
        self.stacked = {n: flat[n] for n in LAYER_LEAVES}
        self.plain = {n: a for n, a in flat.items() if n not in LAYER_LEAVES}
        self.make_start = make_start
        self.cfg, self.dims = cfg, dims_of(cfg)
        self.opt = (float(opt["lr"]), float(opt["betas"][0]),
                    float(opt["betas"][1]), float(opt["eps"]),
                    float(opt["weight_decay"]))
        self.head_chunk = head_chunk
        self.g1: Dict[Any, Any] = {}
        self.losses: List[float] = []
        self.grad_sq: Dict[Any, Any] = {}
        self.seconds: Dict[str, float] = {}

    @property
    def flat(self) -> Dict[str, Any]:
        return {**self.stacked, **self.plain}

    def _update_plain(self, name, g, step):
        self.plain[name], gsq = _apply_leaf(
            self.plain[name], g, self.g1.get(name), step, self.opt)
        if step == 1:
            self.g1[name], self.grad_sq[(name, None)] = g, gsq

    def _update_layer(self, l, g, step):
        self.stacked, gsq = _apply_layer(
            self.stacked, l, g, self.g1.get(l), step, self.opt)
        if step == 1:
            self.g1[l] = g
            self.grad_sq.update({(n, l): v for n, v in gsq.items()})

    def _timed(self, what, t0, *ready):
        import time

        jax.block_until_ready(ready)
        now = time.perf_counter()
        self.seconds[what] = self.seconds.get(what, 0.0) + now - t0
        return now

    def step(self, ids: np.ndarray, labels: np.ndarray, pos: np.ndarray,
             seg: np.ndarray) -> float:
        """One optimizer step on rows ``ids`` [B, S]."""
        import time

        step = len(self.losses) + 1
        if step > 2:
            raise ValueError("the reference follows two steps")
        n_layers = self.cfg["num_hidden_layers"]
        ids, labels, pos, seg = (jnp.asarray(a, jnp.int32)
                                 for a in (ids, labels, pos, seg))
        t = time.perf_counter()
        h = self.plain["embed"][ids].astype(F32)
        inputs = []
        for l in range(n_layers):
            inputs.append(h)
            h = _layer_fwd(_take_layer(self.stacked, l), h, pos, seg,
                           self.dims)
        t = self._timed("forward", t, h)
        tokens = h.shape[0] * h.shape[1]
        chunk = self.head_chunk if tokens % self.head_chunk == 0 else tokens
        loss, gn, gh, dh = _head(
            self.plain["final_norm"].astype(F32),
            self.plain["lm_head"].astype(F32), h, labels, self.dims[3], chunk)
        del h
        self._update_plain("final_norm", gn, step)
        self._update_plain("lm_head", gh, step)
        del gn, gh
        t = self._timed("head", t, dh, self.plain)
        for l in reversed(range(n_layers)):
            gp, dh = _layer_bwd(_take_layer(self.stacked, l), inputs.pop(),
                                pos, seg, dh, self.dims)
            self._update_layer(l, gp, step)
            del gp
        self._update_plain(
            "embed", _embed_grad(ids, dh, self.plain["embed"]), step)
        self._timed("backward", t, self.stacked, self.plain)
        self.losses.append(float(loss))
        return self.losses[-1]

    def grad_norms(self) -> Dict[Any, float]:
        """|g| of the first step per (leaf, layer)."""
        return {k: float(np.sqrt(v)) for k, v in self.grad_sq.items()}

    def change_norms(self) -> Dict[Any, float]:
        """|p_now - p_start| per (leaf, layer); frees Adam's state first."""
        self.g1.clear()
        return change_norms(self.flat, self.make_start())


def leaf_norm_arrays(flat: Dict[str, Any], minus: Dict[str, Any] = None,
                     scale: float = 1.0) -> Dict[str, Any]:
    """|scale * (flat - minus)| of every leaf of weight-shaped flat dicts:
    one number per layer for a stacked leaf, one for the others.
    Traceable."""
    out = {}
    for name, a in flat.items():
        d = a.astype(F32)
        if minus is not None:
            d = d - minus[name].astype(F32)
        axes = tuple(range(1, d.ndim)) if name in LAYER_LEAVES else None
        out[name] = jnp.sqrt(jnp.sum(d * d, axis=axes)) * scale
    return out


def norm_dict(arrays: Dict[str, Any]) -> Dict[Any, float]:
    """{(leaf, layer or None): norm} from :func:`leaf_norm_arrays`."""
    out = {}
    for name, a in arrays.items():
        a = np.asarray(a)
        if a.ndim:
            out.update({(name, l): float(v) for l, v in enumerate(a)})
        else:
            out[(name, None)] = float(a)
    return out


_one_leaf = jax.jit(lambda name, a, b: leaf_norm_arrays(
    {name: a}, {name: b})[name], static_argnums=0)


def change_norms(now: Dict[str, Any], start: Dict[str, Any]):
    """|now - start| per (leaf, layer), one leaf at a time."""
    return norm_dict({n: _one_leaf(n, now[n], start[n]) for n in now})
