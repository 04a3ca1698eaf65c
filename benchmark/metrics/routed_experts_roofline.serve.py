"""The routed experts against their roofline, for any family whose
reference states an expert's size: the least time the chip could take for
the experts hit and the assignments made (the program's own
``serve_experts`` counts), over the device time of the operations under the
``moe_experts`` scope."""
from benchmark import common
from benchmark.metrics import _latent_moe as lm
from benchmark.rooflines import routed_experts


def read(ctx):
    events, ns = lm.expert_events(ctx), lm.scope_ns(ctx, lm.MOE_EXPERTS)
    if not events or not ns:
        return None
    flops, bytes_ = routed_experts.work(ctx["config"], events)
    chips, peaks = ctx["device"]["count"], ctx["peaks"]
    t_flops = flops / chips / peaks["bf16_flops_per_s"]
    t_bytes = bytes_ / chips / peaks["hbm_bytes_per_s"]
    common.say(f"routed_experts: device {1e-9 * ns:.4f} s; least "
               f"{max(t_flops, t_bytes):.4f} s, bound by "
               f"{'compute' if t_flops >= t_bytes else 'bandwidth'}")
    return 100.0 * max(t_flops, t_bytes) / (1e-9 * ns)
