"""Public serving surface: engine, config, request/output types, and the
paged-KV primitives (allocator, prefix index) callers may introspect."""
from .config import EngineConfig, EngineError                  # noqa: F401
from .engine import (Engine, EngineStats,                      # noqa: F401
                     percentile_stats, quantize_params)
from .request import (FinishReason, Request, RequestOutput,    # noqa: F401
                      SamplingParams, Status)
from .scheduler import Scheduler                               # noqa: F401

from repro.core.paged_kvcache import (                         # noqa: F401
    BlockAllocator, OutOfBlocksError, PagedKVCache, PrefixIndex)
