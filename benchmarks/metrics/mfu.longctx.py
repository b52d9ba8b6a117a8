"""Forward model operations of the tokens prefilled and generated in the window over window x peak: attention counted at the selected keys (min(t + 1, topk) a query), the indexer at every visible key, the head once a prompt and once a decoded token, experts at top_k x held / experts (host clock; the family's serve_flops and request_pairs over the pump's per-request records)."""
import metric_lib


def read(ctx):
    sv, family, s = ctx["serve"], ctx["family"], ctx["sizes"]
    began = [r for r in ctx["pump"]["rec"] if r["at_close"] > 0]
    pairs = [family.request_pairs(s, r["n0"], r["at_close"]) for r in began]
    flops = family.serve_flops(
        s, prefill_tokens=sv["flops"]["prefill_tokens"],
        decode_tokens=sv["flops"]["decode_tokens"], prompts=len(began),
        visible_pairs=sum(p[0] for p in pairs),
        selected_pairs=sum(p[1] for p in pairs))
    return 100.0 * flops / (sv["closed"] * metric_lib.chips(ctx)
                            * metric_lib.peak(ctx)["flops_per_s"])
