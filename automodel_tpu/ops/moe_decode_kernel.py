"""Pallas routed experts of a decode step — the ``moe_decode.pallas`` rung.

A decode step routes a few dozen tokens over a layer's experts, so what an
expert costs is the read of its weights (``ops/moe.decode_expert_ffn`` owns
the contract).  One Mosaic call runs the whole layer:

* **only hit experts are read.**  The experts that got a token (in id order)
  and their number ride scalar prefetch with ``layer``; the grid is ``(E,
  steps of one expert)`` and slot ``s`` works on ``hit[s]``.  A slot past the
  number of hit experts maps every operand to the block the slot before it
  left (no DMA) and skips its compute: an expert nobody chose costs a few
  empty grid steps, never its weights.  The stacks of ALL layers stay where
  they are, the index maps pick ``(layer, expert)``.
* **no gather, no scatter-add.**  Every hit expert takes the step's whole
  ``[T, H]`` block; a ``[T, E]`` float32 combine matrix (zero where a token did
  not choose the expert: a padded row's and a sentinel's assignments are
  nowhere in it) weights its result into ``out [T, H]``, accumulated in
  float32 in VMEM over all experts and written once.
* **contiguous slabs, back to back.**  An expert is ``nH`` steps over ``[tH,
  I]`` slabs of gate and up (accumulating ``[T, I]`` in float32), the
  activation, then ``nI`` steps over ``[tI, H]`` slabs of down.  Each operand's
  block index changes at the very step that first needs the new block, so the
  pipeline fetches it one step ahead, across the boundary between two
  experts too, and a DMA is in flight under every step's products.

Numbers: operands in the compute dtype, float32 accumulation, the hidden
activation rounded to the compute dtype once before the down product, the
combine weight applied in float32.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from automodel_tpu.ops.kernel_lib import autotune, registry, tiling

# Pallas interpret mode: the CPU suite runs the real kernel logic.
_INTERPRET = False

# Every hit expert multiplies all T rows of the step.  A weight element
# passes through a matrix unit once per 128 rows whatever their number below
# that, so up to 128 rows the products take weights at 4 units x 128
# elements a cycle x 1.5 GHz x 2 B = 1.5 TB/s against the 0.82 TB/s that HBM
# delivers them at (v5e), and the DMA hides them; at 256 rows they pass twice
# (0.77 TB/s) and the rows, not the weights, would set the time.  Wider steps
# keep the loop over an expert's own rows.
MAX_ROWS = 128
_SLAB_BYTES = 4 * 1024 * 1024       # one step's weight DMA, about
_VMEM_BUDGET = 48 * 1024 * 1024     # under tiling's 64 MB ceiling


def moe_decode_available(rows: int, hidden: int, inter: int) -> bool:
    if not 1 <= rows <= MAX_ROWS or hidden % tiling.LANE \
            or inter % tiling.LANE:
        return False
    if _INTERPRET:
        return True
    return registry.on_tpu()


def _slab_rows(n: int, row_bytes: int) -> int:
    """The most rows of an ``[n, ...]`` matrix, a lane multiple dividing
    ``n``, whose slab stays under ``_SLAB_BYTES`` (never under one lane)."""
    best = tiling.LANE
    for t in range(tiling.LANE, n + 1, tiling.LANE):
        if n % t == 0 and t * row_bytes <= _SLAB_BYTES:
            best = t
    return best


def _vmem_bytes(t: int, h: int, i: int, th: int, ti: int, wsize: int,
                csize: int) -> int:
    """The call's working set: double-buffered slabs of the three matrices,
    the step's block and its output twice, the accumulators."""
    return (2 * (2 * th * i + ti * h) * wsize
            + 2 * t * h * csize * 2
            + t * (2 * i + h) * 4 + t * i * csize)


def _tiles(t: int, h: int, i: int, wsize: int, csize: int) -> Tuple[int, int]:
    """``(tH, tI)``: gate and up are walked in ``[tH, I]`` slabs (both at a
    step), down in ``[tI, H]`` slabs, each about ``_SLAB_BYTES`` so that a
    step's DMA (~5 us) dwarfs its fixed cost (~0.35 us) while the first and
    the last slab of a call, which nothing overlaps, stay small beside an
    expert.  A persisted autotune winner (kernel key ``"moe_decode"``)
    overrides when it divides the shape and fits."""
    default = (_slab_rows(h, 2 * i * wsize), _slab_rows(i, h * wsize))
    return autotune.lookup(
        "moe_decode", {"t": t, "h": h, "i": i}, default,
        validate=lambda c: (
            len(c) == 2 and c[0] % tiling.LANE == 0 and h % c[0] == 0
            and c[1] % tiling.LANE == 0 and i % c[1] == 0
            and _vmem_bytes(t, h, i, c[0], c[1], wsize, csize)
            <= _VMEM_BUDGET))


def _kernel(layer_ref, hit_ref, n_ref, x_ref, c_ref, wg_ref, wu_ref, wd_ref,
            o_ref, hg, hu, hid, acc, *, n_h: int, n_i: int, act, cd):
    from jax.experimental import pallas as pl

    s, j = pl.program_id(0), pl.program_id(1)
    live = s < n_ref[0]

    @pl.when((s == 0) & (j == 0))
    def _start():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(live & (j < n_h))
    def _gate_up():
        x = x_ref[j]                                        # [T, tH]
        g = jnp.dot(x, wg_ref[...].astype(cd),
                    preferred_element_type=jnp.float32)     # [T, I]
        u = jnp.dot(x, wu_ref[...].astype(cd),
                    preferred_element_type=jnp.float32)

        @pl.when(j == 0)
        def _first():
            hg[...] = g
            hu[...] = u

        @pl.when(j > 0)
        def _rest():
            hg[...] += g
            hu[...] += u

    @pl.when(live & (j == n_h))
    def _activate():
        a = (act(hg[...]) * hu[...]).astype(cd)             # rounded once
        ti = a.shape[1] // n_i
        for t in range(n_i):
            hid[t] = a[:, t * ti:(t + 1) * ti]

    @pl.when(live & (j >= n_h))
    def _down():
        y = jnp.dot(hid[j - n_h], wd_ref[...].astype(cd),
                    preferred_element_type=jnp.float32)     # [T, H]
        c = c_ref[...]                                      # [T, E padded]
        col = lax.broadcasted_iota(jnp.int32, c.shape, 1)
        w = jnp.sum(jnp.where(col == hit_ref[s], c, 0.0), axis=1,
                    keepdims=True)                          # [T, 1] float32
        acc[...] += y * w

    @pl.when((s == pl.num_programs(0) - 1) & (j == n_h + n_i - 1))
    def _finish():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def _combine_matrix(weights, idx, num_experts: int):
    """``(combine [T, E] float32, tokens_per_expert [E] int32)``: a token's
    share of each expert, nought where it did not choose it.  One fused
    compare-select-reduce each; the sentinel id ``E`` (a padded row, an
    expert held elsewhere) matches no column."""
    chose = idx[:, :, None] == jnp.arange(num_experts, dtype=idx.dtype)
    combine = jnp.sum(jnp.where(chose, weights.astype(jnp.float32)[:, :, None],
                                0.0), axis=1)
    return combine, jnp.sum(chose, axis=(0, 1), dtype=jnp.int32)


def _hit_experts(sizes: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``sizes [E]`` -> ``(hit [E], n [1])``: the ids of the experts with a
    token in id order, the rest of the list repeating the last of them (a
    slot past ``n`` then addresses what its neighbour did), and their
    number.  With no token at all the list is expert 0's."""
    E = sizes.shape[0]
    ids = jnp.arange(E, dtype=jnp.int32)
    got = sizes > 0
    rank = jnp.cumsum(got.astype(jnp.int32)) - 1            # place in the list
    hit = jnp.sum(jnp.where(got[None, :] & (rank[None, :] == ids[:, None]),
                            ids[None, :], 0), axis=1)
    n = jnp.sum(got.astype(jnp.int32))
    last = jnp.max(jnp.where(got, ids, 0))
    return jnp.where(ids < n, hit, last).astype(jnp.int32), n.reshape(1)


def moe_decode_pallas(x, weights, idx, w_gate, w_up, w_down, layer, *,
                      compute_dtype, activation: str):
    """``x [T, H]``, ``weights/idx [T, k]`` over layer ``layer`` of the
    stacks ``[L, E, H, I]`` / ``[L, E, I, H]`` -> ``(out [T, H] in the
    compute dtype, tokens_per_expert [E] int32)`` (module docstring)."""
    from jax.experimental.pallas import tpu as pltpu
    from jax.experimental import pallas as pl

    from automodel_tpu.ops.moe import ACTIVATIONS

    T, H = x.shape
    E, I = w_gate.shape[1], w_gate.shape[3]
    cd = jnp.dtype(compute_dtype)
    assert T <= MAX_ROWS, "moe_decode is the decode-width rung"
    Tp = -(-T // 16) * 16           # whole sublane tiles of a 2-byte row
    Ep = -(-E // tiling.LANE) * tiling.LANE
    th, ti = _tiles(Tp, H, I, w_gate.dtype.itemsize, cd.itemsize)
    n_h, n_i = H // th, I // ti

    combine, sizes = _combine_matrix(weights, idx, E)
    combine = jnp.pad(combine, ((0, Tp - T), (0, Ep - E)))
    hit, n = _hit_experts(sizes)
    # [nH, T, tH]: a step takes its columns of x by a leading index
    xs = jnp.pad(x.astype(cd), ((0, Tp - T), (0, 0)))
    xs = xs.reshape(Tp, n_h, th).transpose(1, 0, 2)

    def up_index(s, j, layer, hit, n):
        live = s < n[0]
        return (layer[0], hit[s],
                jnp.where(live, jnp.minimum(j, n_h - 1), n_h - 1), 0)

    def down_index(s, j, layer, hit, n):
        # before a slot's own down steps the block is the one the slot
        # before it left: it changes at the step that needs it, and the
        # pipeline fetches it under the last gate/up step
        live = s < n[0]
        own = live & (j >= n_h)
        e = jnp.where(own, hit[s], hit[jnp.maximum(s - 1, 0)])
        first = live & (s == 0)
        return (layer[0], e, jnp.where(
            own, j - n_h, jnp.where(first, 0, n_i - 1)), 0)

    whole = lambda *shape: tiling.block_spec(
        shape, lambda s, j, *_: (0,) * len(shape))
    out = pl.pallas_call(
        functools.partial(_kernel, n_h=n_h, n_i=n_i,
                          act=ACTIVATIONS[activation], cd=cd),
        grid_spec=tiling.prefetch_grid_spec(
            num_scalar_prefetch=3,
            grid=(E, n_h + n_i),
            in_specs=[
                whole(n_h, Tp, th),
                whole(Tp, Ep),
                tiling.block_spec((None, None, th, I), up_index),
                tiling.block_spec((None, None, th, I), up_index),
                tiling.block_spec((None, None, ti, H), down_index),
            ],
            out_specs=whole(Tp, H),
            scratch_shapes=[
                pltpu.VMEM((Tp, I), jnp.float32),
                pltpu.VMEM((Tp, I), jnp.float32),
                pltpu.VMEM((n_i, Tp, ti), cd),
                pltpu.VMEM((Tp, H), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((Tp, H), cd),
        compiler_params=tiling.compiler_params(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_INTERPRET,
    )(jnp.asarray(layer, jnp.int32).reshape(1), hit, n, xs, combine,
      w_gate, w_up, w_down)
    return out[:T], sizes


def _moe_decode_probe(request) -> bool:
    """Decode-width steps of unquantized, lane-aligned experts whose stacks
    lie whole on the one device that runs the step."""
    if request.get("quantized") or request.get("devices", 1) != 1:
        return False
    return moe_decode_available(request["rows"], request["hidden"],
                                request["inter"])


def _moe_decode_impl(request, x, weights, idx, w_gate, w_up, w_down, layer,
                     *, compute_dtype, activation, quant=None):
    # XLA:TPU names a Mosaic custom call after the innermost component of
    # its scope path: ``moe_decode`` is the name to read in a trace.
    with jax.named_scope("moe_decode"):
        return moe_decode_pallas(
            x, weights, idx, w_gate, w_up, w_down, layer,
            compute_dtype=compute_dtype, activation=activation)


def moe_decode_reference(request, x, weights, idx, w_gate, w_up, w_down,
                         layer, *, compute_dtype, activation, quant=None):
    """Dense XLA oracle: every expert of the layer on every token in
    float32, weighted by the token's share of it (nought where it did not
    choose it)."""
    from automodel_tpu.ops.moe import ACTIVATIONS

    f32 = lambda a: a.astype(jnp.float32)
    wg, wu, wd = (f32(w[layer]) for w in (w_gate, w_up, w_down))
    hp = lax.Precision.HIGHEST
    xf = f32(x)
    hidden = (ACTIVATIONS[activation](
        jnp.einsum("th,ehi->eti", xf, wg, precision=hp))
        * jnp.einsum("th,ehi->eti", xf, wu, precision=hp))
    y = jnp.einsum("eti,eih->eth", hidden, wd, precision=hp)
    share, counts = _combine_matrix(weights, idx, w_gate.shape[1])
    out = jnp.einsum("te,eth->th", share, y, precision=hp)
    return out.astype(compute_dtype), counts


registry.register_kernel(
    "moe_decode.pallas", probe=_moe_decode_probe, impl=_moe_decode_impl,
    fallback="moe_decode.loop", reference=moe_decode_reference)
