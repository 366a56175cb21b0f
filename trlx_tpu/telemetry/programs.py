"""The process's named device programs, and which scope each op of each
belongs to.

A profile names every executed program by its module (``jit_rollout_fn``,
``jit_run_decode_step``) and every operation by its instruction name
(``fusion.12``); the program's parts are the ``jax.named_scope`` paths in
each instruction's metadata (``jit(rollout_fn)/rollout/score/trunk/...``).
This module joins the two: where a program is compiled it registers once,
under its module name, and :func:`op_scopes` reads that executable's
optimized text on the first request and keeps ``{instruction: op_name}``.

Registering keeps a way to the text and nothing else: nothing is lowered,
compiled or parsed until someone asks, and asking compiles nothing new.
``utils.aotjit`` registers the executable it compiled; :func:`jit` (the
trainers' plain ``jax.jit``) keeps its first call's abstract signature, and
a request lowers that signature again, which JAX's lowering cache answers
with the executable the dispatches ran. The record outlives the programs'
owners (a benchmark reads it after the trainer or engine is released);
one entry a module name, the newest registration winning.

The executable's metadata is that of the tree that compiled it: JAX's
persistent compile cache leaves metadata out of its key, so two trees whose
programs differ only in their scopes share entries.
"""

import functools
import re
from typing import Callable, Dict, Optional

import jax

from trlx_tpu.utils.hlo_text import op_names

__all__ = ["jit", "module_name", "op_scopes", "register"]

_TEXT: Dict[str, Callable[[], str]] = {}
_SCOPES: Dict[str, Dict[str, str]] = {}


def module_name(fun) -> str:
    """The module name JAX gives ``jax.jit(fun)``'s executable: ``jit(<name>)``
    made a symbol (``rollout_fn`` -> ``jit_rollout_fn``)."""
    while getattr(fun, "__name__", None) is None and isinstance(fun, functools.partial):
        fun = fun.func
    name = getattr(fun, "__name__", "<unnamed function>")
    return re.sub(r"[^\w.\-]", "_", f"jit({name})").rstrip("_")


def register(module: str, text: Callable[[], str]) -> None:
    """Record ``module``; ``text()`` gives its optimized HLO text on request."""
    _TEXT[module] = text
    _SCOPES.pop(module, None)


def op_scopes(module: str) -> Optional[Dict[str, str]]:
    """``{instruction: op_name}`` of the registered executable ``module``, or
    None if no such program was compiled in this process."""
    if module not in _SCOPES:
        text = _TEXT.get(module)
        if text is None:
            return None
        _SCOPES[module] = op_names(text())
    return _SCOPES[module]


def _abstract(x):
    """What a jitted call keys its executable on: shape, dtype, weak type, and
    the sharding of an argument committed to its devices."""
    aval = jax.typeof(x)
    committed = isinstance(x, jax.Array) and x._committed
    return jax.ShapeDtypeStruct(aval.shape, aval.dtype, weak_type=aval.weak_type,
                                sharding=x.sharding if committed else None)


class RecordedJit:
    """``jax.jit(fun, **jit_kwargs)`` whose executable :func:`op_scopes` can
    read: it registers itself at its first call, and every call is the jitted
    function's own dispatch after one check. Positional arguments only (the
    trainers' step programs)."""

    def __init__(self, fun, **jit_kwargs):
        self._jitted = jax.jit(fun, **jit_kwargs)
        self._module = module_name(fun)
        self._recorded = False

    def __call__(self, *args):
        if not self._recorded:
            self._record(args)
        return self._jitted(*args)

    def _record(self, args):
        sig = jax.tree_util.tree_map(_abstract, args)
        register(self._module, lambda: self._jitted.lower(*sig).compile().as_text())
        self._recorded = True

    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    def trace(self, *args, **kwargs):
        return self._jitted.trace(*args, **kwargs)

    def _cache_size(self):
        return self._jitted._cache_size()


jit = RecordedJit
