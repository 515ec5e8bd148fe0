from .engine import (PromptTooLongError, Request, ServeConfig, ServingEngine,
                     pod_local_cache_rules, prefix_key, validate_prompt)
from .paged import (BlockAllocator, BlockLeakError, PagedServeConfig,
                    PagedServingEngine, kv_token_bytes)
from .router import PrefixRouter

__all__ = ["PromptTooLongError", "Request", "ServeConfig", "ServingEngine",
           "pod_local_cache_rules", "prefix_key", "validate_prompt",
           "BlockAllocator", "BlockLeakError", "PagedServeConfig",
           "PagedServingEngine", "kv_token_bytes", "PrefixRouter"]
