"""Where the ``lax.scan`` loops of a traced function stand: which scan
is inside which, read off the jaxpr.

The jaxpr and not the compiled text, because the backends differ in
what they do with it: XLA:CPU moves a loop whose inputs do not change
out of the loop around it, XLA:TPU leaves it where the program put it
(measured on v5e: the PPO update's 46-layer frozen trunk, run once per
epoch of four). A test on a CPU that reads compiled text or a timing
cannot tell the two programs apart; the jaxpr says what was written.
"""

import math
from typing import Iterator, NamedTuple, Tuple

from jax.extend import core as jex_core

__all__ = ["ScanSite", "scan_sites"]


class ScanSite(NamedTuple):
    length: int  # the scan's own trip count
    enclosing: Tuple["ScanSite", ...]  # the scans around it, outermost first
    scope: str  # the named scopes down to it ("update/trunk")

    @property
    def runs(self) -> int:
        """How many times a call of the traced function runs this loop."""
        return math.prod(s.length for s in self.enclosing)


def _sub_jaxprs(params):
    for value in params.values():
        for v in value if isinstance(value, (tuple, list)) else (value,):
            if isinstance(v, jex_core.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, jex_core.Jaxpr):
                yield v


def scan_sites(jaxpr, enclosing=(), scope="") -> Iterator[ScanSite]:
    """Every ``scan`` of ``jaxpr`` (a ``Jaxpr`` or ``ClosedJaxpr``) and of
    the jaxprs nested in it (jit, remat, custom derivatives, the branches
    of a cond, a while's body: each counted as run once)."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        here = "/".join(
            s for s in (scope, str(eqn.source_info.name_stack)) if s
        )
        inner = enclosing
        if eqn.primitive.name == "scan":
            site = ScanSite(eqn.params["length"], enclosing, here)
            yield site
            inner = enclosing + (site,)
        for sub in _sub_jaxprs(eqn.params):
            yield from scan_sites(sub, inner, here)
