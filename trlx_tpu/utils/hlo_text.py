"""What a compiled executable's text (``compiled.as_text()``) says the
compiler did with the program's large operands.

A dot streams a weight from HBM once; an op that first writes the weight
out again in another layout is a pass of its own over it, every time the
program runs. XLA:TPU asks for that where it folds a reshape into a
dot and the folded dot wants the weight in another order (measured on
v5e: the q/k/v weights of a gpt-j-6B decode step, a third of the step).
:func:`large_moves` lists such ops so a warm-up gauge and a compile-only
test can hold the count at nought. :func:`whiles_by_computation` says
which loop the compiler left inside which: XLA:TPU does not move a loop
whose inputs never change out of the loop around it (measured on v5e:
the PPO update's frozen trunk inside its scan over epochs), so a
compile-only test holds the trunk's loop in the entry computation.
"""

import re
from typing import Dict, List, NamedTuple, Tuple

__all__ = ["ENTRY", "Move", "While", "large_moves", "whiles_by_computation"]

#: `` %name = <result type> opcode(`` of one instruction line
_INSTR = re.compile(r"^\s+(?:ROOT )?%(\S+) = (.*?) ([\w\-]+)\(")
#: one array of a result type (a fusion's may be a tuple of them): dtype, dims
_ARRAY = re.compile(r"\b([a-z]+\d\w*|pred)\[([\d,]*)\]")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) ")
_FUSED = re.compile(r" fusion\(.*?calls=%([\w.\-]+)")
_BODY = re.compile(r"\bbody=%([\w.\-]+)")

#: the key of the entry computation in :func:`whiles_by_computation`
ENTRY = "ENTRY"


class Move(NamedTuple):
    kind: str  # copy | transpose | slice_bitcast_fusion
    nbytes: int  # bytes the op writes
    name: str  # the instruction's name
    op_name: str  # its metadata: the named scopes down to the jax op


def _nbytes(dtype: str, dims: str) -> int:
    bits = re.search(r"\d+", dtype)
    n = max(int(bits.group()) // 8, 1) if bits else 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def large_moves(text: str, min_bytes: int) -> List[Move]:
    """The ops of a compiled program that only move data (``copy``,
    ``transpose``, and the fusions XLA names ``slice_bitcast_fusion``:
    slices of a stacked operand written out on their own) and write at
    least ``min_bytes``. ``copy-start`` is not among them: every one seen
    in this repo's programs keeps its layout and changes the memory space
    (``S(n)``), the asynchronous prefetch a dot then reads in HBM's
    place, which is how a weight is streamed. Instructions inside a
    fusion's body are part of that fusion and are not ops of their
    own."""
    fused = set(_FUSED.findall(text))
    moves, computation = [], None
    for line in text.splitlines():
        if not line.startswith(" "):
            header = _COMPUTATION.match(line)
            if header:
                computation = header.group(1)
            continue
        if computation in fused:
            continue
        instr = _INSTR.match(line)
        if not instr:
            continue
        name, result, opcode = instr.groups()
        if opcode == "fusion" and name.startswith("slice_bitcast_fusion"):
            kind = "slice_bitcast_fusion"
        elif opcode in ("copy", "transpose"):
            kind = opcode
        else:
            continue
        nbytes = sum(_nbytes(*array) for array in _ARRAY.findall(result))
        if nbytes >= min_bytes:
            op_name = _OP_NAME.search(line)
            moves.append(Move(kind, nbytes, name,
                              op_name.group(1) if op_name else ""))
    return moves


class While(NamedTuple):
    name: str  # the instruction's name
    body: str  # the computation it runs: a key of the same dict if that holds loops too
    carry: Tuple[Tuple[str, Tuple[int, ...]], ...]  # (dtype, dims) of each array it carries


def whiles_by_computation(text: str) -> Dict[str, List[While]]:
    """The ``while`` ops of a compiled program by the computation they
    stand in, the entry computation under :data:`ENTRY`. A loop inside a
    loop is found under its outer loop's ``body``; a ``lax.scan`` over
    stacked weights carries them, so ``carry`` says whose loop it is
    (``("bf16", (46, 1600, 6400))``: 46 stacked layers)."""
    whiles: Dict[str, List[While]] = {}
    computation = None
    for line in text.splitlines():
        if not line.startswith(" "):
            header = _COMPUTATION.match(line)
            if header:
                computation = (ENTRY if line.startswith("ENTRY ")
                               else header.group(1))
            continue
        instr = _INSTR.match(line)
        if not instr or instr.group(3) != "while":
            continue
        name, result, _ = instr.groups()
        carry = tuple(
            (dtype, tuple(int(d) for d in dims.split(",") if d))
            for dtype, dims in _ARRAY.findall(result)
        )
        whiles.setdefault(computation, []).append(
            While(name, _BODY.search(line).group(1), carry)
        )
    return whiles
