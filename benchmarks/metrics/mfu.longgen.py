"""Forward model operations of the tokens prefilled and generated in the window over window x peak: the recurrence of each state-space layer a token, attention over the causal pairs of the attention layers, the head once a prompt and once a decoded token (host clock; the family's serve_flops over the pump's counts)."""
import metric_lib


def read(ctx):
    sv = ctx["serve"]
    began = sum(1 for r in ctx["pump"]["rec"] if r["at_close"] > 0)
    flops = ctx["family"].serve_flops(ctx["sizes"], **sv["flops"],
                                      prompts=began)
    return 100.0 * flops / (sv["closed"] * metric_lib.chips(ctx)
                            * metric_lib.peak(ctx)["flops_per_s"])
