"""Shared by the `<kernel>_roofline.*` readers: a kernel's share of its
roofline is the least time the chip could take for the work, max(FLOPs /
peak, bytes / bandwidth), over the device time of the kernel's events."""

from __future__ import annotations

from benchmark import common, trace_reduce


def roofline_share(ctx, roofline, steps):
    """None (the metric is left out, and the run says so aloud) where no
    device operation bears the kernel's name, or more names do than the
    programs that ran can hold kernels: time that is not the kernel's would
    be summed in."""
    reduced = ctx["reduced"]
    opcode = getattr(roofline, "OPCODE", None)
    names = trace_reduce.names_matching(reduced, roofline.EVENTS, opcode)
    most = roofline.NAMES_PER_PROGRAM * max(1, len(reduced["modules"]))
    if not names or len(names) > most or not steps:
        common.say(f"WARNING {roofline.__name__}: {len(names)} operation "
                   f"names match {roofline.EVENTS!r} ({names[:6]}), at most "
                   f"{most} could be the kernel's in programs "
                   f"{sorted(reduced['modules'])}: nothing sound to read, "
                   "the metric is left out")
        return None
    seconds = trace_reduce.seconds_matching(reduced, roofline.EVENTS, opcode)
    flops, bytes_ = roofline.work(ctx["config"], steps)
    chips, peaks = ctx["device"]["count"], ctx["peaks"]
    t_flops = flops / chips / peaks["bf16_flops_per_s"]
    t_bytes = bytes_ / chips / peaks["hbm_bytes_per_s"]
    common.say(f"{roofline.__name__}: kernel {names} {seconds:.4f} s; least "
               f"{max(t_flops, t_bytes):.4f} s, bound by "
               f"{'compute' if t_flops >= t_bytes else 'bandwidth'}")
    return 100.0 * max(t_flops, t_bytes) / seconds
