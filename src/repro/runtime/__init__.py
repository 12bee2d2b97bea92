"""repro.runtime — one Engine/Backend API for float, LUT and Pallas execution.

Owns execution policy end to end: which numeric path runs the model
(``Backend`` registry), how params are quantised (``QuantRecipe``), and
the single planning entry point ``compile_model(cfg, params,
backend=..., recipe=...) -> Engine``.  No call site outside this package
mutates ``softmax_mode`` / ``act_approx`` or calls ``quantize_tree``
directly — see README §repro.runtime for the migration table.
"""

from repro.runtime.backends import (Backend, available_backends, get_backend,
                                    plan_interpret, register_backend)
from repro.runtime.compile_cache import compile_cache_dir, enable_compile_cache
from repro.runtime.engine import Engine, EngineHandle, compile_model
from repro.runtime.recipe import QuantRecipe


def quantize_params(params, cfg, rounding: str = "nearest"):
    """Compat shim for the old ``launch.serve.quantize_params``: PTQ per
    paper §IV (int8 weights at the Table V exponent, norms/biases float),
    returned as the dequantised float tree the engine runs."""
    return QuantRecipe.from_config(cfg, rounding=rounding).apply(params)


__all__ = ["Backend", "Engine", "EngineHandle", "QuantRecipe",
           "available_backends", "compile_cache_dir", "compile_model",
           "enable_compile_cache", "get_backend", "plan_interpret",
           "quantize_params", "register_backend"]
