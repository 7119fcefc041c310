"""The top-1 expert sublayers' share of their roofline: the least time the
chip could take for the experts' products over the traced steps' routed
pairs, over the device time of the expert sublayers' operations
(``moe_top1.device_ms_per_step``'s: the router network, the sort and the
combine are in the time and not in the floor). ``moe.expert_roofline_share``'s
contract, for a configuration whose sizes ``harness/cca.py`` reads.

The work is counted by the configuration's reference module from the engine's
routing counters, so it is the same whatever implements the products: the
weights of every expert a program's tokens reached, read once a program and
layer (``moe_experts_hit``), each routed pair's input and output row, and the
pairs' FLOPs (``moe_pairs_held``: one a token and layer). The least time is the
larger of bytes / bandwidth and FLOPs / peak."""

from harness import cca, hybrid, peaks


def read(ctx):
    seconds = cca.device_seconds(ctx, "moe")
    routing = cca.traced_routing(ctx)
    if not seconds or routing is None:
        return None
    cfg = ctx["cfg"]
    ref = hybrid.reference_for(cfg)
    least, bound = peaks.roofline_seconds(
        ref.expert_flops(cfg, routing["moe_pairs_held"]),
        ref.expert_min_bytes(
            cfg, routing["moe_experts_hit"], routing["moe_pairs_held"]),
        ctx["device_kind"])
    ctx["moe_top1_roofline_bound"] = bound
    return 100.0 * least / seconds
