"""The family `gpt2`: everything of the benchmark that depends on the
architecture of a GPT-2 configuration. A configuration file names its
family (`"family": "gpt2"`); the harness finds this package by that name
(`find.family`) and asks it, and nothing else, for what is below. Another
architecture is another package beside this one that defines the same
names; the harness reads no key of a configuration but `family`.

| name | what the harness asks of it |
|---|---|
| `sizes(cfg)` | the configuration's sizes from its own keys, among them `vocab` (the harness draws the traffic's ids under it and checks the answers against it) and `positions` (the longest context); the rest is for the family's own functions, which get the dict back |
| `TOY_CFG` | the configuration's keys at the toy size of `--rehearse` |
| `serving_model(cfg, mix, seed)` | the `Layer` for `InferenceEngine`, loaded with the seed's weights; refuses a `mix["weights"]` the family has not proven on the chip |
| `training_model(cfg, mix, seed)` | `layer`, `loss`, `parameters`, `names` for `TrainStep` and the optimizer, loaded alike |
| `assert_routes(model, cfg, mix, rehearse)` | set-up fails if the cell's shapes fall off the kernel path |
| `make(cfg, seed, form)`, `split_fused(tree)` | the seed's weights `by_name` (the program's leaves) or `stacked` (the reference's), and the leaves as `correct` compares them |
| `served_gaps(...)`, `train_steps(...)` | the plain reference, and the controls it knows by the names mixes use (`bf16`, `fp8`) |
| `train_flops_per_token`, `serve_flops`, `kv_bytes_per_token`, `decode_step_bytes`, `matmul_params`, `total_params`, `flash_call_shape` | the counts, from `sizes` |
"""
from .counts import (decode_step_bytes, flash_call_shape,  # noqa: F401
                     kv_bytes_per_token, matmul_params, serve_flops,
                     total_params, train_flops_per_token)
from .model import (PROVEN_WEIGHTS, assert_routes,  # noqa: F401
                    serving_model, training_model)
from .reference import served_gaps, train_steps  # noqa: F401
from .weights import make, sizes, split_fused  # noqa: F401

#: toy sizes of the CPU rehearsal; widths here have no meaning
TOY_CFG = {"vocab_size": 503, "n_positions": 128, "n_ctx": 128,
           "n_embd": 128, "n_layer": 2, "n_head": 4, "n_inner": 512}
