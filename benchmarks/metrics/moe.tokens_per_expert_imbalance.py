"""How unevenly the router loads the held experts: the most tokens any held
expert of a layer got over the mean a held expert got, from the engine's
routing counters (``moe_tokens_per_expert_max`` over ``_mean``, each summed
over the programs and layers of the steps that started in the traced window:
a mean over layers and steps weighted by their tokens). 1 is even. With
random weights the routing is uniform and this reads what sampling 8.9 tokens
an expert gives; a deployment's is more uneven."""

from harness import moe_hybrid


def read(ctx):
    routing = moe_hybrid.traced_routing(ctx)
    if routing is None or not routing.get("moe_tokens_per_expert_mean"):
        return None
    return (routing["moe_tokens_per_expert_max"]
            / routing["moe_tokens_per_expert_mean"])
