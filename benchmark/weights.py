"""The benchmark's weights: made on the device from ``--seed`` in one jitted
call, in the type they are served or trained in.

The weights are an INPUT of a run, like its tokens: the benchmark makes
them as a flat dict, hands that to the plain reference as it is and to the
program in the program's tree layout.  Neither side takes anything the
other has made.  Which leaves a configuration has, and where each sits in
the program's tree, is its family's to say (``reference/<model_type>.py``:
``leaf_shapes``, ``to_program_tree``); nothing here names a family.

Matrices are normal(0, 0.02); norm weights are 1 + 0.1 * normal, so that a
norm applied at the wrong width or place changes the result.  Every value
is rounded to bfloat16 (the stored type the configurations state), so the
float32 reference starts from exactly the program's numbers.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

MATRIX_STD = 0.02
NORM_STD = 0.1


def head_dim(cfg: Dict[str, Any]) -> int:
    return int(cfg.get("head_dim")
               or (cfg.get("assumed") or {}).get("head_dim")
               or cfg["hidden_size"] // cfg["num_attention_heads"])


def seed_words(seed: int):
    """``--seed`` (any whole number up to a little over 2**31) as two 32-bit
    words.  They are an ARGUMENT of the jitted maker, not a constant in it:
    one compiled program makes the weights of every seed, so a new seed
    finds its program in the compile cache."""
    import numpy as np

    seed = int(seed)
    return np.array([seed & 0x7FFFFFFF, seed >> 31], np.uint32)


def make(shapes: Dict[str, Tuple[Tuple[int, ...], str]],
         words) -> Dict[str, Any]:
    """The flat dict of bfloat16 weights from :func:`seed_words`, one leaf
    per entry of ``shapes`` (name -> (shape, "matrix"|"norm"), in a fixed
    order).  Traceable: call it under ``jax.jit`` with ``words`` as an
    argument, so that it is one program on the device."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(jax.random.key(words[0]), words[1])
    out = {}
    for i, (name, (shape, kind)) in enumerate(shapes.items()):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        w = z * MATRIX_STD if kind == "matrix" else 1.0 + NORM_STD * z
        # an explicit rounding: where this is traced into a larger program
        # XLA drops a float32 -> bfloat16 -> float32 pair of conversions
        # and would hand on the unrounded numbers
        out[name] = jax.lax.reduce_precision(w, 8, 7).astype(jnp.bfloat16)
    return out
