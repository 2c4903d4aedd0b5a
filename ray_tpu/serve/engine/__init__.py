"""ray_tpu.serve.engine: device-resident LLM inference engine.

- ``core``         — ``InferenceEngine``: the engine-thread tick, alone.
- ``decode_loop``  — the jitted programs (prefill, K-step decode scan
  with EOS/budget termination ON DEVICE, verify, KV pages) and what an
  optional mechanism needs of a family (``check_offers``).
- ``kv_manager``   — slots, block occupancy, hash-based prefix cache.
- ``scheduler``    — model-free continuous-batching admission.
- ``metrics``      — counters, the tick's clock, the device's queue.
- ``drafter`` (``Speculation``), ``handoff`` (``KVHandoff``: the
  prefill/decode roles), ``preempt`` (``Preemption``), ``kv_fleet``
  (``FleetTier``; imported only when the tier is on) — the four
  mechanisms the tick calls out to, one object each.

See README.md in this package for the architecture notes;
``serve/llm.py`` remains the compatibility facade (``LLMEngine``).
"""

from ray_tpu.serve.engine.core import InferenceEngine
from ray_tpu.serve.engine.decode_loop import DecodeLoop
from ray_tpu.serve.engine.drafter import PromptLookupDrafter, SpecControl
from ray_tpu.serve.engine.kv_manager import KVCacheManager
from ray_tpu.serve.engine.metrics import EngineMetrics
from ray_tpu.serve.engine.scheduler import (Admission, EngineRequest,
                                            Scheduler, bucket_for)

__all__ = [
    "Admission", "DecodeLoop", "EngineMetrics", "EngineRequest",
    "InferenceEngine", "KVCacheManager", "PromptLookupDrafter",
    "Scheduler", "SpecControl", "bucket_for",
]
