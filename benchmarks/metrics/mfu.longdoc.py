"""Forward model operations of the tokens prefilled and generated in the window over window x peak; the head counts once a prompt and once a decoded token, so the family is told how many prompts the window began (host clock; the family's serve_flops)."""
import metric_lib


def read(ctx):
    sv = ctx["serve"]
    prompts = sum(1 for r in ctx["pump"]["rec"] if r["at_close"] > 0)
    flops = ctx["family"].serve_flops(ctx["sizes"], prompts=prompts,
                                      **sv["flops"])
    return 100.0 * flops / (sv["closed"] * metric_lib.chips(ctx)
                            * metric_lib.peak(ctx)["flops_per_s"])
