from repro.serve.engine import (ElasticNetEngine, EnResult,
                                greedy_generate, make_decode_step,
                                make_prefill_step)

__all__ = [
    "ElasticNetEngine",
    "EnResult",
    "make_decode_step",
    "make_prefill_step",
    "greedy_generate",
]
