from .engine import ServeEngine
from .sampling import GenerationResult, Request, SamplingParams
from .scheduler import SPANS, Scheduler

__all__ = ["ServeEngine", "Scheduler", "Request", "SamplingParams",
           "GenerationResult", "SPANS"]
# precision autotuning + self-speculative decoding live in
# repro.serve.autotune (imported lazily by the engine/CLIs to keep the
# base serve import light)
