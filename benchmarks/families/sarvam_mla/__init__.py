"""The family `sarvam_mla`: everything of the benchmark that depends on
the architecture of a latent-attention (MLA), routed-expert decoder as
`sarvamai/sarvam-105b` publishes it. What a family defines is listed in
`families/gpt2/__init__.py`; this one defines what the `serve` driver and
its metric readers ask (it is not trained here: AdamW's state does not fit
one chip at any depth worth measuring, PERF.md section 4).

| name | here |
|---|---|
| `sizes(cfg)`, `TOY_CFG` | `weights.py`; `held` is the experts this chip holds (`num_experts` of the file), `experts` the router's published width (`published.num_experts`) |
| `serving_model`, `assert_routes`, `PROVEN_WEIGHTS` | `model.py`: `serving.LatentMoELM` in bfloat16, the only precision proven; set-up fails off the blockwise / absorbed / dropless routes |
| `make`, `split_fused` | `weights.py`: a leaf at a time, bfloat16-valued |
| `served_gaps` | `reference.py`: float32 at `highest`, expanded attention in blocks, the held experts a plain loop; control `fp8`; `faults` for the fault tests |
| `serve_flops`, `kv_bytes_per_token`, `decode_step_bytes`, `matmul_params`, `total_params` | `counts.py`, of the algorithm |
"""
from .counts import (decode_step_bytes, experts_reached,  # noqa: F401
                     kv_bytes_per_token, matmul_params, serve_flops,
                     total_params)
from .model import (PROVEN_WEIGHTS, assert_routes,  # noqa: F401
                    serving_model)
from .reference import served_gaps  # noqa: F401
from .weights import make, sizes, split_fused  # noqa: F401

#: toy sizes of the CPU rehearsal; widths here have no meaning
TOY_CFG = {
    "vocab_size": 503, "max_position_embeddings": 128, "hidden_size": 64,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 16,
    "q_head_dim": 32, "v_head_dim": 16, "kv_lora_rank": 32, "head_dim": 48,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_experts": 4, "num_experts_per_tok": 4, "num_shared_experts": 1,
    "published": {"num_experts": 16, "num_hidden_layers": 3,
                  "vocab_size": 503},
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "deepseek_yarn"},
    "key_block": 32}
