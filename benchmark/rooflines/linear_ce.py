"""Work of the fused linear cross-entropy: tokens x hidden x vocabulary,
forward (logits) and backward (hidden and head gradients); the logits the
backward recomputes are not credited.  Compute-bound."""

from __future__ import annotations

# the program's pallas_calls carry no name of their own: in a train step
# they appear as the custom_vjp's `jvp__` (forward) and `transpose_jvp___`
# (the two backward kernels); PERF.md asks the tracing PR for stable names
EVENTS = r"^(transpose_)?jvp_+(\.\d+)?$"
OPCODE = "custom-call"      # Pallas kernels, not another jvp of no name
NAMES_PER_PROGRAM = 3       # the forward kernel and the two backward ones


def work(cfg, steps):
    tokens = sum(int((s != 0).sum()) for segs in steps for s in segs)
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    flops = 6 * tokens * h * v
    # the head is read forward and twice backward and its gradient written;
    # hidden states are read three times and their gradient written
    bytes_ = len(steps) * 4 * h * v * 2 + 4 * tokens * h * 2
    return flops, bytes_
