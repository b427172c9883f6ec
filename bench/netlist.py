"""Product tables of 8x8-bit multipliers, simulated from their gate
netlists: the reference's view of a library entry, whatever the model.

It reads each circuit's netlist and nothing the program computed from
it, so a fault in the program's tables cannot pass into the reference.
"""
from __future__ import annotations

import numpy as np

#: gate function codes of the CGP function set, in the paper's Fig. 1
#: order: identity, not, and, or, xor, nand, nor, xnor, const0, const1
_GATES = (
    lambda a, b: a,
    lambda a, b: ~a,
    lambda a, b: a & b,
    lambda a, b: a | b,
    lambda a, b: a ^ b,
    lambda a, b: ~(a & b),
    lambda a, b: ~(a | b),
    lambda a, b: ~(a ^ b),
    lambda a, b: np.zeros_like(a),
    lambda a, b: np.ones_like(a),
)
_ARITY = (1, 1, 2, 2, 2, 2, 2, 2, 0, 0)


def netlist_lut(n_i: int, funcs, in0, in1, outputs) -> np.ndarray:
    """(256, 256) int32 product table of an 8x8-bit multiplier netlist:
    every node evaluated on all 2^16 input pairs.  Input bit ``i < 8``
    is bit ``i`` of the row operand, input ``8 + i`` bit ``i`` of the
    column operand; output ``k`` is bit ``k`` of the product.  A gate
    reads only the inputs its function takes: the unused input fields
    of a stored netlist may point anywhere."""
    if n_i != 16:
        raise ValueError(f"an 8x8-bit multiplier has 16 inputs, not {n_i}")
    v = np.arange(1 << 16, dtype=np.int64)
    sig = [((v >> i) & 1).astype(bool) for i in range(n_i)]
    for f, a, b in zip(funcs, in0, in1):
        arity = _ARITY[int(f)]
        x = sig[int(a)] if arity >= 1 else sig[0]
        y = sig[int(b)] if arity == 2 else x
        sig.append(_GATES[int(f)](x, y))
    out = np.zeros(1 << 16, np.int64)
    for k, s in enumerate(outputs):
        out |= sig[int(s)].astype(np.int64) << k
    # v = row + 256 * col
    return out.reshape(256, 256).T.astype(np.int32)


def exact_lut() -> np.ndarray:
    a = np.arange(256, dtype=np.int32)
    return a[:, None] * a[None, :]


def tables(library):
    """``lut_of(name)``: the product table of the library entry
    ``name``, simulated once per name."""
    memo: dict = {}

    def lut_of(name: str) -> np.ndarray:
        if name not in memo:
            nl = library.entry(name).netlist
            memo[name] = netlist_lut(nl.n_i, nl.funcs, nl.in0, nl.in1,
                                     nl.outputs)
        return memo[name]
    return lut_of
