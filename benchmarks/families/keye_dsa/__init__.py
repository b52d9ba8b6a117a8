"""The family `keye_dsa`: everything of the benchmark that depends on the
architecture of a grouped-query decoder with a learned sparse-attention
indexer (DeepSeek-Sparse-Attention) and softmax top-k routed experts, as
`Kwai-Keye/Keye-VL-2.0-30B-A3B` publishes its language model. What a
family defines is listed in `families/gpt2/__init__.py`; this one defines
what the `serve` driver and its metric readers ask (it is not trained
here: the indexer is trained by a distillation loss the config has no
key for, PERF.md section 4).

| name | here |
|---|---|
| `sizes(cfg)`, `TOY_CFG` | `weights.py`; `held` is the experts this chip holds (`num_experts` of the file), `experts` the router's published width (`published.num_experts`) |
| `serving_model`, `assert_routes`, `PROVEN_WEIGHTS` | `model.py`: `serving.SparseMoELM` in bfloat16, the only precision proven; set-up fails off the masked blockwise / gather / dropless softmax routes |
| `make`, `split_fused` | `weights.py`: a leaf at a time, bfloat16-valued |
| `served_gaps` | `reference.py`: float32 at `highest`, `jax.lax.top_k` for both selections, attention a block of queries at a time over gathered rows, the held experts a plain loop; control `fp8` |
| `serve_flops`, `request_pairs`, `kv_bytes_per_token`, `decode_step_bytes`, `experts_reached`, `matmul_params`, `total_params` | `counts.py`, of the algorithm: attention at the selected keys, the indexer at every visible key |
"""
from .counts import (decode_step_bytes, experts_reached,  # noqa: F401
                     kv_bytes_per_token, matmul_params, request_pairs,
                     serve_flops, total_params)
from .model import (PROVEN_WEIGHTS, assert_routes,  # noqa: F401
                    serving_model)
from .reference import served_gaps  # noqa: F401
from .weights import make, sizes, split_fused  # noqa: F401

#: toy sizes of the CPU rehearsal; widths here have no meaning. Contexts
#: of the toy mix run to 100 tokens over a `topk` of 16 and tiles of 32
#: keys, so the selection bites and the blockwise forms walk four tiles.
#: `rehearsal_weights`: the toy runs float32. With 16 keys a query, one
#: near-tied selection that bfloat16 rounds the other way is a sixteenth
#: of the query's attention, and the sound program's own `token_gap_pow4`
#: (2.6e-10 to 1.2e-7 over four seeds) reaches the mildest fault's
#: (5.7e-7); at the cell's size it is one key of 2,048. The band
#: bfloat16 earns at a small size is tests/test_sparse_moe_lm.py's.
TOY_CFG = {
    "rehearsal_weights": "float32",
    "vocab_size": 503, "max_position_embeddings": 128, "hidden_size": 64,
    "num_hidden_layers": 2, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_experts": 4, "num_local_experts": 4,
    "num_experts_per_tok": 4, "rope_theta": 10000,
    "sa_config": {"indexer_head_dim": 16, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 32,
                  "q_chunk_size": 32, "topk": 16},
    "published": {"num_experts": 16, "num_local_experts": 16,
                  "num_hidden_layers": 2, "vocab_size": 503}}
