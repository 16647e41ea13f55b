from repro.serve.kvcache import PagedKVCache, PageAllocator
from repro.serve.scheduler import SalpScheduler, Request
from repro.serve.engine import ServingEngine

__all__ = ["PagedKVCache", "PageAllocator", "SalpScheduler", "Request",
           "ServingEngine"]
