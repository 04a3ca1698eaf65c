"""Share of the window's slots that held no token: one minus the non-zero
segment ids over all segment ids of the batches the window's steps were
given (``runners/train.py`` counts them).  The device computes every slot
and ``train_tok_s_chip`` credits none of these."""


def read(ctx):
    return 100.0 * ctx["window"]["padding_share"]
