"""The family ``smallthinker`` (a configuration's ``model_type`` finds this
file): SmallThinker-21BA3B's decoder (arXiv:2507.20984) — window and full
(NoPE) attention layers in one stack, every feed-forward a routed block of
small ReLU-gated experts behind a router that reads the PRE-attention
stream — which weights it has and where each sits in the program's
parameter tree, the work a step requires of it, and its plain reference.
Serving only (training at 16 B a parameter fits one chip only at the
guide's floors: PERF.md section 4).

The plain reference: float32 at ``highest`` matmul precision, ``jax.numpy``
and ``jax.lax`` only, attention over the whole sequence a block of queries
at a time, no cache, no kernels, every expert computed for every token and
weighted (0 where not chosen).  It imports nothing of the program.

The equations, for layer ``l`` on a stream ``x [T, H]``, eps
``rms_norm_eps``, ``G = Hq / Hk`` query heads a key/value head::

    u  = rmsnorm(x, w_in)
    q  = u Wq [T,Hq,d]    k = u Wk [T,Hk,d]    v = u Wv [T,Hk,d]
    if rope_layout[l] == 1:  q, k = rope(q, k)      # else NoPE
    visible(i, j) = j <= i  and, if sliding_window_layout[l] == 1,
                    i - j < sliding_window_size
    a  = softmax(q k^T / sqrt(d) over visible keys) v   # head h reads kv head h // G
    h  = x + a Wo
    m  = rmsnorm(h, w_post)
    r  = u Wr [T,E]          # the router reads u, NOT m
    S  = top-k of r ;  p = softmax_float32(r[S])
    y  = sum_{e in S} p_e (relu(m G_e) * (m U_e)) D_e
    x' = h + y
    logits = rmsnorm(x_L, w_f) W_head                    # untied

``config.json`` does not state, and the configuration's file lists under
``assumed`` as "from the published ``modeling_smallthinker.py``, quoted from
memory, not on this machine": no bias and no q/k norm in attention; RoPE
over the whole head with rotate-half pairing ``(i, i + d/2)`` at
``rope_theta``; the router's input ``u`` (``described_as``: "router placed
before attention"); the experts' form (ReGLU, no bias); softmax over the
CHOSEN logits (``moe_primary_router_apply_softmax`` with ``norm_topk_prob``).
Not in this configuration: secondary experts and the LM-head sparsity
predictor that the paper describes (the catalog row's ``config`` has
neither key), and ``model_type`` itself (the catalog strips it).

*What a served token can show, and what it cannot.*  Top-k is a step
function: where the k-th and the (k+1)-th logit of a token lie within
round-off of each other (half their distance under ``TIE`` of the token's
root-mean-square logit), the bfloat16 program and this float32 reference
may choose differently, and one expert swapped for another moves the
logits by more than any rounding does.  Which way such a tie falls says
nothing of the program.  As ``reference/kimi_k2.py`` does,
:func:`served_token_gaps` compares every UNTIED token in full and the tied
tokens of a request through one number, the ``TIED_QUANTILE`` of their
gaps.  Layer 0 is the one layer whose ties are NOT independent draws: its
router logits are a function of the token id alone, greedy decoding of
random weights settles into a dozen tokens, and a token whose k-th and
(k+1)-th logit tie there ties at every position that holds it (seed
38214120 on the chip: one such token at 108 of a request's 1,143 positions,
25 % of its tokens over 0.02, the request read 0.148).  That is why the
program's router reads the norm's float32 result, not its bfloat16 rounding
(``models/smallthinker.py``): an embedding row is the same numbers in both
programs, so layer 0's logits then agree to float32 round-off and its ties
fall the same way in both (the same seed then read 0.009; PERF.md
section 2).  Deeper layers' margins differ from position to position.
All ``E`` experts are held here and every layer routes, so a token
is tied if ANY of its layers' margins is under ``TIE``: most tokens of a
deep stack are (PERF.md section 2 has the shares measured on the chip:
under a band of 1 % a sound run in eight still read 0.06-0.09 from one or
two flipped tokens whose margin lay between 1 % and 3 %; none of ~60
requests had one past 3 %).

``attention_pair_flops`` is ONE number a (query, key) pair, and the
runner's ``attended`` counts every key before a query: ``mfu.serve``
therefore credits the window layers with the pairs past the window too.
At the cell's mix (mean context ~4.6 k a row, window layers reading 67 % of
it: ``window_keys_share.serve``, my chip run, PR 35) that over-credits
attention by ``6/8 x 33 % = 25 %`` of its FLOPs, which are 24 % of a decode
step's required FLOPs (114,688 a pair against 1.68 GFLOP of matrix products
a position): ``mfu.serve`` reads ~6 % of itself too high (1.62 % for
~1.53 %).  ``hybrid_paged_decode_roofline.serve`` counts the keys a window
shows (the program's ``serve_kv_read`` events).
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
Q_BLOCK = 256           # queries whose scores are live at a time
TOKEN_BLOCK = 2048      # tokens an expert multiplies at a time
TIE = 3e-2              # half the k-th to (k+1)-th logit distance, as a
                        # share of the token's root-mean-square router
                        # logit, under which round-off decides the choice
TIED_QUANTILE = 0.95    # of a request's tied tokens' gaps: stands for each
MIN_TIED = 40           # fewer: the quantile is their max; they are left out

LAYER_LEAVES = ("input_norm", "q_proj", "k_proj", "v_proj", "o_proj",
                "post_norm", "router", "e_gate", "e_up", "e_down")


# ---------------------------------------------------------------------------
# The family's weights, its layout in the program, its required work
# ---------------------------------------------------------------------------
def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    win = [int(w) for w in cfg["sliding_window_layout"]]
    return dict(
        H=cfg["hidden_size"], Hq=cfg["num_attention_heads"],
        Hk=cfg["num_key_value_heads"], D=head_dim(cfg),
        I=cfg["moe_ffn_hidden_size"], E=cfg["moe_num_primary_experts"],
        held=cfg["moe_num_primary_experts"],
        k=cfg["moe_num_active_primary_experts"], V=cfg["vocab_size"],
        L=cfg["num_hidden_layers"], n_moe=cfg["num_hidden_layers"],
        n_window=sum(win), n_full=len(win) - sum(win),
        W=cfg["sliding_window_size"])


def leaf_shapes(cfg: Dict[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, "matrix"|"norm"), in a fixed order.  Per-layer leaves
    carry the layer count as their first axis."""
    z = sizes(cfg)
    L, H, D, I, E = z["L"], z["H"], z["D"], z["I"], z["E"]
    if len(cfg["rope_layout"]) != L or len(cfg["sliding_window_layout"]) != L:
        raise ValueError("rope_layout and sliding_window_layout must hold "
                         f"num_hidden_layers={L} entries")
    return {
        "embed": ((z["V"], H), "matrix"),
        "input_norm": ((L, H), "norm"),
        "q_proj": ((L, H, z["Hq"] * D), "matrix"),
        "k_proj": ((L, H, z["Hk"] * D), "matrix"),
        "v_proj": ((L, H, z["Hk"] * D), "matrix"),
        "o_proj": ((L, z["Hq"] * D, H), "matrix"),
        "post_norm": ((L, H), "norm"),
        "router": ((L, H, E), "matrix"),
        "e_gate": ((L, E, H, I), "matrix"),
        "e_up": ((L, E, H, I), "matrix"),
        "e_down": ((L, E, I, H), "matrix"),
        "final_norm": ((H,), "norm"),
        "lm_head": ((H, z["V"]), "matrix"),
    }


def make(cfg: Dict[str, Any], words) -> Dict[str, Any]:
    """The flat dict of this configuration's weights (``weights.make``).
    ``assumed.qk_gain`` (a toy's file only) multiplies ``q_proj`` and
    ``k_proj``: a score's spread is ``0.02^2 x hidden_size`` at these
    weights, 1.0 at the published 2560 and 0.03 at a toy's 64, where
    attention would be uniform and no comparison could see a fault in what
    a query may see or in how it is rotated."""
    flat = weights.make(leaf_shapes(cfg), words)
    gain = (cfg.get("assumed") or {}).get("qk_gain")
    if gain:
        for name in ("q_proj", "k_proj"):
            flat[name] = lax.reduce_precision(
                flat[name].astype(F32) * gain, 8, 7).astype(jnp.bfloat16)
    return flat


def to_program_tree(flat: Dict[str, Any]) -> Dict[str, Any]:
    """The flat dict in the layout of ``automodel_tpu.models.smallthinker``
    (stacked layers, ``[in, out]`` kernels) — the one place the benchmark
    names the program's parameter tree."""
    kernel = lambda n: {"kernel": flat[n]}
    weight = lambda n: {"weight": flat[n]}
    return {
        "embed_tokens": {"embedding": flat["embed"]},
        "layers": {
            "input_layernorm": weight("input_norm"),
            "self_attn": {"q_proj": kernel("q_proj"),
                          "k_proj": kernel("k_proj"),
                          "v_proj": kernel("v_proj"),
                          "o_proj": kernel("o_proj")},
            "post_attention_layernorm": weight("post_norm"),
            "block_sparse_moe": {
                "primary_router": kernel("router"),
                "experts": {"gate": kernel("e_gate"), "up": kernel("e_up"),
                            "down": kernel("e_down")}},
        },
        "norm": weight("final_norm"),
        "lm_head": kernel("lm_head"),
    }


def from_program_tree(tree: Dict[str, Any]) -> Dict[str, Any]:
    """Inverse of :func:`to_program_tree`."""
    lay, att = tree["layers"], tree["layers"]["self_attn"]
    moe = lay["block_sparse_moe"]
    return {
        "embed": tree["embed_tokens"]["embedding"],
        "input_norm": lay["input_layernorm"]["weight"],
        "q_proj": att["q_proj"]["kernel"], "k_proj": att["k_proj"]["kernel"],
        "v_proj": att["v_proj"]["kernel"], "o_proj": att["o_proj"]["kernel"],
        "post_norm": lay["post_attention_layernorm"]["weight"],
        "router": moe["primary_router"]["kernel"],
        "e_gate": moe["experts"]["gate"]["kernel"],
        "e_up": moe["experts"]["up"]["kernel"],
        "e_down": moe["experts"]["down"]["kernel"],
        "final_norm": tree["norm"]["weight"],
        "lm_head": tree["lm_head"]["kernel"],
    }


def model_config(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The keys of a configuration file that ``build_model`` takes."""
    keys = ("model_type", "vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "moe_ffn_hidden_size", "moe_num_active_primary_experts",
            "moe_num_primary_experts", "moe_primary_router_apply_softmax",
            "norm_topk_prob", "rope_layout", "sliding_window_layout",
            "sliding_window_size", "rope_theta", "rope_scaling",
            "max_position_embeddings", "rms_norm_eps", "tie_word_embeddings")
    return {k: cfg[k] for k in keys if k in cfg}


def expert_params(cfg: Dict[str, Any]) -> int:
    """One expert's three matrices: 5,898,240 at the published widths."""
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def matmul_params(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameters that sit in a matrix product for ONE position, per layer
    and in the head: attention, the router and the k ACTIVE experts (all E
    are held, a position runs k of them)."""
    z = sizes(cfg)
    attn = z["H"] * (z["Hq"] + 2 * z["Hk"]) * z["D"] + z["Hq"] * z["D"] * z["H"]
    layer = attn + z["H"] * z["E"] + z["k"] * expert_params(cfg)
    return {"layer": layer, "layers": layer * z["L"],
            "head": z["H"] * z["V"], "expert": expert_params(cfg)}


def attention_pair_flops(cfg: Dict[str, Any]) -> int:
    """Forward FLOPs per (query, key) pair over ALL layers, QK^T and PV: one
    number a pair, so a window layer's pairs past the window are credited
    too (the module docstring says by how much at the cell's mix)."""
    z = sizes(cfg)
    return 4 * z["Hq"] * z["D"] * z["L"]


def kv_bytes_per_token_layer(cfg: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """K and V of one token in one layer: 2,048 B at the published widths."""
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


def rope(x, pos, theta):
    """x [T, heads, D], pos [T]: rotate pairs (i, i + D/2)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window, block: int = Q_BLOCK):
    """Causal softmax attention on one sequence, a block of queries at a
    time: ``q [T, Hq, d]``, ``k, v [T, Hk, d]`` -> ``[T, Hq, d]``; ``window``
    (0: none) keys behind a query are visible, itself among them.  ``T`` is
    a multiple of ``block`` or below it."""
    t, hq, d = q.shape
    hk = k.shape[1]
    block = min(block, t)
    qg = q.reshape(t // block, block, hk, hq // hk, d)
    at = jnp.arange(t)

    def one(args):
        qb, tb = args                               # [block, Hk, G, d], [block]
        s = jnp.einsum("thgd,shd->hgts", qb, k) * (d ** -0.5)
        seen = tb[:, None] >= at[None, :]
        if window:
            seen &= tb[:, None] - at[None, :] < window
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hgts,shd->thgd", p, v)

    out = lax.map(one, (qg, at.reshape(-1, block)))
    return out.reshape(t, hq, d)


def experts(p, u, m, k: int):
    """``(y [T, H], margin [T])``: the routed sum, every expert computed for
    every token and weighted by its share of the softmax over the token's
    ``k`` chosen logits (0 where not chosen); and half the distance from
    the k-th to the (k+1)-th logit over the root-mean-square of the token's
    logits (a logit's round-off grows with them; under ``TIE``: round-off
    chooses)."""
    r = u @ p["router"]                                       # [T, E]
    top, chosen = lax.top_k(r, k + 1)
    share = jax.nn.softmax(top[:, :k], axis=-1)
    t = m.shape[0]
    b = min(TOKEN_BLOCK, t)
    mb = m.reshape(t // b, b, -1)

    def one(e, acc):
        w = jnp.sum(jnp.where(chosen[:, :k] == e, share, 0.0), axis=-1)
        g, up, dn = (p[n][e].astype(F32) for n in ("e_gate", "e_up", "e_down"))
        y = lax.map(lambda x: (jax.nn.relu(x @ g) * (x @ up)) @ dn, mb)
        return acc + w[:, None] * y.reshape(t, -1)

    y = lax.fori_loop(0, p["router"].shape[-1], one, jnp.zeros_like(m))
    scale = jnp.sqrt(jnp.mean(r * r, axis=-1))
    return y, 0.5 * (top[:, k - 1] - top[:, k]) / scale


def dims_of(cfg: Dict[str, Any]):
    z = sizes(cfg)
    return (z["Hq"], z["Hk"], z["D"], float(cfg["rms_norm_eps"]),
            float(cfg["rope_theta"]), z["k"], int(z["W"]))


def layer_params(flat, l):
    """Layer ``l`` in float32; the expert stacks stay bfloat16 and are cast
    one expert at a time where they are used."""
    return {n: (flat[n][l] if n.startswith("e_") else flat[n][l].astype(F32))
            for n in LAYER_LEAVES}


@functools.partial(jax.jit, static_argnames=("dims", "rotary", "window"))
@highest
def layer(p, h, dims, rotary: bool, window: bool):
    """One decoder layer on one sequence, its kind static: ``h [T, H]`` ->
    ``(h', margin [T])``."""
    hq, hk, d, eps, theta, k, w = dims
    t = h.shape[0]
    u = rms_norm(h, p["input_norm"], eps)
    q = (u @ p["q_proj"]).reshape(t, hq, d)
    kk = (u @ p["k_proj"]).reshape(t, hk, d)
    v = (u @ p["v_proj"]).reshape(t, hk, d)
    if rotary:
        pos = jnp.arange(t, dtype=jnp.int32)
        q, kk = rope(q, pos, theta), rope(kk, pos, theta)
    a = attention(q, kk, v, w if window else 0).reshape(t, hq * d)
    h = h + a @ p["o_proj"]
    m = rms_norm(h, p["post_norm"], eps)
    y, margin = experts(p, u, m, k)
    return h + y, margin


def hidden_states(flat, cfg, ids, by_layer: bool = False):
    """ids [T] -> ``(final-normed hidden [T, H], margin [T])``: one full
    forward, layer by layer; the margin is the least over the layers
    (``by_layer``: every layer's, ``[L, T]``)."""
    dims = dims_of(cfg)
    h = flat["embed"][ids].astype(F32)
    margins = []
    for l in range(cfg["num_hidden_layers"]):
        h, here = layer(layer_params(flat, l), h, dims,
                        bool(cfg["rope_layout"][l]),
                        bool(cfg["sliding_window_layout"][l]))
        margins.append(here)
    margins = jnp.stack(margins)
    return (_final_norm(flat["final_norm"], h, dims[3]),
            margins if by_layer else jnp.min(margins, axis=0))


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
    the ``TIED_QUANTILE`` of the tied tokens' (0 where they are fewer than
    ``MIN_TIED``).  Returns the gaps and that quantile."""
    among = (float(np.quantile(gaps[tied], TIED_QUANTILE))
             if tied.sum() >= MIN_TIED else 0.0)
    return np.where(tied, among, gaps), among


def served_token_gaps(flat, cfg, prompt: Sequence[int],
                      served: Sequence[int], pad_to: int = 2048,
                      rows: int = 2048) -> np.ndarray:
    """The gap of every served token of one request, teacher-forced through
    ONE full forward over prompt + served tokens; the tied tokens (module
    docstring) each carry the ``TIED_QUANTILE`` of theirs.  The sequence is
    padded on the right (causal, so the pad changes nothing) to a multiple
    of ``pad_to`` so that few programs are compiled; the logits exist
    ``rows`` positions at a time."""
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
    # tokens, too few for the percentile, so a wide band would leave nearly
    # all of them out of the comparison
    tie = (cfg.get("assumed") or {}).get("tie", TIE)
    tied = margin < tie
    out, among = gaps_by_the_rule(gaps, tied)
    print(f"[bench] smallthinker reference: of {len(served)} served tokens "
          f"{int((~tied).sum())} have no tie (widest gap "
          f"{float(np.max(gaps, where=~tied, initial=0.0)):.4f}) and "
          f"{int(tied.sum())} a layer whose k-th and (k+1)-th router logit "
          f"lie within {2 * tie:g} of their rms (their {TIED_QUANTILE:.0%} "
          f"quantile "
          f"{among:.4f}, widest "
          f"{float(np.max(gaps, where=tied, initial=0.0)):.4f}); gaps over "
          "0.02 by margin under 1e-3, 3e-3, 1e-2, 3e-2, any: "
          + ", ".join(f"{int(((gaps > 0.02) & (margin < c)).sum())}/"
                      f"{int((margin < c).sum())}"
                      for c in (1e-3, 3e-3, 1e-2, 3e-2, np.inf)), flush=True)
    return out
