"""Work of the power-retention core in a serving step, as the mathematics
requires it whatever implements it: every row a step runs reads its whole
state ``S [D, dv]`` and ``z [D]`` of every key/value head once and writes
it once (``D = d (d + 1) / 2``, the symmetric embedding; a program that
keeps the full ``d x d`` square moves twice that and can read 50 % at
most), and every position costs a multiply and an add per entry for each
key/value head's update and each query head's read-out.  At Brumby-14B's
widths that is 68.2 MB and 102 MFLOP a row a layer for a decode step: 1.5
FLOPs a byte, far under the v5e's ridge, so bandwidth bounds it."""

from __future__ import annotations

from benchmark import manifest as mf

# XLA:TPU names a Mosaic custom call after the innermost component of its
# scope path; the rungs wrap their pallas_calls in ``retention_decode`` and
# ``retention_chunk``
EVENTS = r"^retention_(decode|chunk)(\.\d+)?$"
OPCODE = "custom-call"
# one call in the model's one layer scan, in each step program (the decode
# program holds ``retention_decode``, the chunk program ``retention_chunk``)
NAMES_PER_PROGRAM = 1


def work(cfg, steps):
    """(FLOPs, bytes) over the engine steps given (runners/serve.py's step
    records: active rows and new positions)."""
    family = mf.family(cfg)
    layers = cfg["num_hidden_layers"]
    bytes_ = sum(s["rows"] for s in steps) * layers * 2 \
        * family.state_bytes_per_row_layer(cfg)
    flops = sum(s["positions"] for s in steps) * layers \
        * family.retention_flops_per_position(cfg)
    return flops, bytes_
