"""The family `granite_hybrid`: everything of the benchmark that depends
on the architecture of a state-space hybrid decoder, as
`ibm-granite/granite-4.0-h-micro` publishes it (`granitemoehybrid` with no
experts): Mamba-2 mixers beside a few grouped-query attention layers with
no position encoding, a dense gated MLP in every layer, a head tied to the
embedding. What a family defines is listed in `families/gpt2/__init__.py`;
this one defines what the `serve` driver and its metric readers ask (it is
not trained here: PERF.md section 4).

| name | here |
|---|---|
| `sizes(cfg)`, `TOY_CFG` | `weights.py` |
| `serving_model`, `assert_routes`, `PROVEN_WEIGHTS` | `model.py`: `serving.HybridSSMLM` in bfloat16, the only precision proven; set-up fails where a prefill chunk would not run the chunked scan or a decode step the one-token step |
| `make`, `split_fused` | `weights.py`: a leaf at a time, bfloat16-valued |
| `served_gaps` | `reference.py`: float32 at `highest`, the state-space layer as its recurrence token by token, attention a block of queries at a time; control `fp8` |
| `serve_flops`, `kv_bytes_per_token`, `state_bytes_per_slot`, `ssm_step_bytes`, `decode_step_bytes`, `matmul_params`, `total_params` | `counts.py`, of the algorithm: the recurrence, not the chunked form |
"""
from .counts import (decode_step_bytes, kv_bytes_per_token,  # noqa: F401
                     matmul_params, serve_flops, ssm_step_bytes,
                     state_bytes_per_slot, total_params)
from .model import (PROVEN_WEIGHTS, assert_routes,  # noqa: F401
                    serving_model)
from .reference import served_gaps  # noqa: F401
from .weights import make, sizes, split_fused  # noqa: F401

#: toy sizes of the CPU rehearsal; widths here have no meaning. Chunks of
#: 16 tokens, so the toy mix's prompts (4-64 tokens, in buckets of 16-64)
#: cross chunks and pad inside them; two attention layers among four.
#: Matrices of normal(0, 0.15) and branches at full weight, so that four
#: layers of width 64 outgrow 12 x the embedding and a served token
#: depends on its context (with the cell's 0.02 and 0.22 the toy answers
#: each token with itself). `rehearsal_weights`: the toy runs float32, so
#: a planted fault stands out of the sound program's own rounding
#: (tests/test_hybrid_ssm_lm.py holds the band bfloat16 earns at a small
#: size).
TOY_CFG = {
    "rehearsal_weights": "float32",
    "vocab_size": 503, "max_position_embeddings": 128, "hidden_size": 64,
    "num_hidden_layers": 4,
    "layer_types": ["mamba", "attention", "mamba", "attention"],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 128, "shared_intermediate_size": 128,
    "mamba_n_heads": 4, "mamba_d_head": 32, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_chunk_size": 16, "initializer_range": 0.15,
    "residual_multiplier": 1.0}
