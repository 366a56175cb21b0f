"""jit wrapper whose executables honor the arguments' committed layouts.

Measured on v5e (jax 0.9): ``jax.jit``'s dispatch path compiles for
DEFAULT entry layouts — an argument carrying a custom at-rest layout
(trlx_tpu.parallel.relayout_for_decode) is relayouted per dispatch and
the program still materializes its own layout-copy temps, as if the
custom layout never existed. The AOT path (``lower().compile()``) keeps
the argument layouts in the executable signature: the gpt-j-6B fused
rollout's HLO temps drop 3.86 GB -> 1.12 GB, the margin between OOM and
fitting on one 16 GB chip.

``aot_jit`` wraps a function with jit semantics but compiles through the
AOT path, caching executables by the full argument signature (tree
structure + per-leaf shape/dtype/layout). The hashing cost is a few
microseconds per call for typical param trees — noise next to the
device programs it dispatches. Each executable it compiles is registered
with ``telemetry.programs`` under its module name.
"""

import jax

from trlx_tpu.telemetry import programs

__all__ = ["aot_jit", "formats_of"]


def formats_of(tree):
    """Per-leaf ``Format`` pytree of concrete arrays — pass as (part of)
    ``out_shardings`` to pin a jit's output layouts to its inputs'
    (donated pass-through subtrees keep their custom at-rest layouts
    instead of silently reverting to XLA's defaults)."""
    return jax.tree_util.tree_map(lambda x: x.format, tree)


def _leaf_sig(x):
    if not hasattr(x, "dtype"):
        # plain-Python leaf (a weak-typed scalar, a string riding a
        # pytree): its VALUE shapes the trace, so it must key the cache
        # the way jit's own cache treats it
        try:
            hash(x)
            return ("py", type(x), x)
        except TypeError:
            return ("py", type(x), repr(x))
    # host leaves (numpy batches riding a dispatch) carry no format
    layout = getattr(
        getattr(getattr(x, "format", None), "layout", None),
        "major_to_minor", None,
    )
    # sharding must join the key: the compiled call path validates arg
    # shardings STRICTLY (plain jit would silently reshard), so an arg
    # whose sharding drifted — e.g. optimizer moments coming back from an
    # unconstrained output — needs its own executable. Weak types key
    # separately for the same reason.
    sharding = getattr(x, "sharding", None)
    weak = getattr(x, "weak_type", False)
    return (x.shape, str(x.dtype), weak, layout, sharding)


class _AotJit:
    def __init__(self, fun, **jit_kwargs):
        self._jitted = jax.jit(fun, **jit_kwargs)
        self._module = programs.module_name(fun)
        self._cache = {}

    def lower(self, *args, **kwargs):  # passthrough for introspection
        return self._jitted.lower(*args, **kwargs)

    def compiled_for(self, *args):
        """The executable for these arguments' signature (compiled on
        first use) — its ``memory_analysis()`` / ``as_text()`` are how a
        caller checks what the compiler made of the program."""
        leaves, treedef = jax.tree_util.tree_flatten(args)
        key = (treedef, tuple(_leaf_sig(x) for x in leaves))
        compiled = self._cache.get(key)
        if compiled is None:
            if self._cache:
                # steady-state miss: an executable already exists but this
                # call's signature (shape/dtype/layout/sharding) matches
                # none of them. A sharding or layout that drifts each step
                # recompiles EVERY dispatch — silent, and seconds per step
                # — so surface it as a counter climbing with iter (telemetry "compile/recompiles"; no-op when
                # telemetry is off). Legitimate new shapes (a differently
                # sized eval batch) add a few counts and then stabilize.
                from trlx_tpu import telemetry

                telemetry.inc("compile/recompiles")
            compiled = self._jitted.lower(*args).compile()
            self._cache[key] = compiled
            programs.register(self._module, compiled.as_text)
        return compiled

    def __call__(self, *args):
        return self.compiled_for(*args)(*args)


def aot_jit(fun, **jit_kwargs):
    """``jax.jit(fun, **jit_kwargs)`` compiled through the AOT path so
    custom argument layouts survive into the executable (module
    docstring). Positional-argument call surface only (the trainers'
    usage); supports the jit kwargs they use (donate_argnums,
    out_shardings)."""
    return _AotJit(fun, **jit_kwargs)
