from .colocate import ColocatedServing
from .engine import DecodeEngine, GenerationResult
from .grounding import GroundingEngine, GroundingResult
from .paged import BlockAllocator, PagedDecodeEngine
from .planner import LongSessionPlanner, PlannerSession
from .radix import RadixCache
from .pp_engine import PPDecodeEngine
from .scheduler import ContinuousBatcher

__all__ = [
    "BlockAllocator",
    "ColocatedServing",
    "ContinuousBatcher",
    "DecodeEngine",
    "GenerationResult",
    "GroundingEngine",
    "GroundingResult",
    "LongSessionPlanner",
    "PagedDecodeEngine",
    "PPDecodeEngine",
    "PlannerSession",
    "RadixCache",
]
