"""paddle_tpu.serving — autoregressive decode + continuous-batching
inference (ISSUE 9 tentpole).

The training side compiles ONE program per step (`jit.TrainStep`); this
package does the same for the decode direction:

- `sampling` — greedy/temperature/top-k/top-p as small traced-safe
  functional ops over raw arrays (RNG-key threaded, per-slot [B]
  parameter vectors so one compiled program serves mixed requests);
- `TransformerLM` (model.py) — the reference-shaped causal LM contract
  `jit.DecodeStep`/`jit.PrefillStep` consume (static-capacity KV cache
  through the `MultiHeadAttention.Cache` seam);
- `generate` / `GenerationConfig` (engine.py) — the whole-batch decode
  loop: bucketed compiled prefill, one compiled single-token step,
  device-resident loop state (ZERO per-token host syncs — tokens come
  back in one transfer at the end or on the stop-check cadence);
- `Request` / `InferenceEngine` (engine.py) — slot-based continuous
  batching over the same compiled pair: insert-on-free scheduling,
  length-bucketed prefill with the bucketed compile cache, per-request
  stop conditions and sampling params, `decode_metrics` telemetry on
  the readback cadence.

Round 13 (ISSUE 13) grows it into the production tier:

- `paged_kv` — fixed-size-block KV pool + per-slot block tables behind
  the same cache seam (HBM tracks actual context, appends are
  defrag-free, freed blocks serve the next request immediately);
- chunked prefill + TTFT accounting in the engine
  (`PADDLE_SERVE_PREFILL_CHUNK`), speculative decoding in `generate`
  (`draft_model=`, `jit.SpeculativeDecodeStep` — greedy token-exact);
- `router` — the multi-host front end: admission control, SLO-aware
  host choice driven by the `decode_metrics` bus rows, a jax-free
  worker for the launcher-driven multi-process dryrun.

Round 15 (ISSUE 15) makes the plane fault-tolerant: the router grows a
per-host health state machine (healthy → suspect → dead / draining →
retired; `PADDLE_SERVE_HOST_TIMEOUT_MS` + exp-backoff probation),
token-exact failover (in-flight requests re-submit to survivors as
`Request(resume_tokens=...)` resume requests under idempotent ids),
live drain (`Router.drain_host` + the `drain`/`cancel` mailbox verbs),
and reasoned load shedding against the surviving fleet; the engine
grows the host-side seam it rides (`InferenceEngine.turn` /
`progress` / `cancel`).

Round 18 (ISSUE 18) makes the plane multi-tenant:

- `prefix_cache` — a refcounted copy-on-write prefix index over the
  paged pool: published prompt blocks become immutable content-hashed
  entries, sharing requests take them by table reference and prefill
  only the unshared tail (`PADDLE_SERVE_PREFIX_CACHE`);
- `adapters` — `AdapterSet` fleets of low-rank fine-tunes resident
  beside the base weights, applied in-graph per slot by a traced
  adapter-id vector (one compiled step for the whole fleet; adapter
  0 is the base model bit-for-bit);
- `router` disaggregation — `PrefillHost`/`FilePrefillHost` run only
  the prefill phase and ship the context as a CRC-gated
  `kv_migration.KVBundle` to a decode host picked by slot
  availability (`PADDLE_SERVE_DISAGG`, `PADDLE_SERVE_ROLE`), falling
  back to colocated admission on any broken rung.
"""
from . import paged_kv  # noqa: F401
from . import sampling  # noqa: F401
from .adapters import AdapterSet  # noqa: F401
from .engine import (  # noqa: F401
    GeneratedResult, GenerationConfig, InferenceEngine, Request, generate,
)
from .model import (  # noqa: F401
    HybridSSMLM, LatentMoELM, SparseMoELM, TransformerLM,
)
from .prefix_cache import PrefixCache  # noqa: F401
from .router import (  # noqa: F401
    FileHost, FilePrefillHost, LocalHost, PrefillHost, Router,
)

__all__ = [
    "sampling", "TransformerLM", "LatentMoELM", "SparseMoELM", "HybridSSMLM",
    "generate",
    "GenerationConfig",
    "Request", "InferenceEngine", "GeneratedResult", "paged_kv",
    "Router", "LocalHost", "FileHost", "PrefillHost", "FilePrefillHost",
    "PrefixCache", "AdapterSet",
]
