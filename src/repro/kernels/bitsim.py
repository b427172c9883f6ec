"""Pallas TPU kernel: bit-parallel gate-netlist simulator.

Accelerates CGP fitness evaluation (DESIGN.md §4.3): each signal holds
one bit per simulated input vector, packed 32 to a uint32 lane.  The
netlist is encoded as flat int32 arrays (funcs/in0/in1/outputs); the
kernel walks the gates with a ``fori_loop`` + ``lax.switch`` writing a
(n_signals, W) scratch in VMEM, evaluating 32 x W input vectors per
grid step with pure bitwise VPU ops — no gather anywhere.

Exhaustive 8x8-multiplier evaluation = 65 536 vectors = 2048 uint32
words; with W-blocks of 512 lanes a ~500-gate netlist needs a
(~516, 512) uint32 scratch ≈ 1 MiB of VMEM.

``bitsim_pop_pallas`` is the population-vectorized variant behind the
device CGP engine (DESIGN.md §2.9): the netlist arrays gain a leading
population axis and the grid gains a population dimension, so every
offspring of an evolutionary generation simulates in ONE program —
each (candidate, W-block) grid step re-uses the same VMEM scratch and
reads its own netlist slice via the BlockSpec index map.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

W_BLOCK = 512
#: candidates per SMEM netlist block of the population kernel (the
#: sublane tile: SMEM blocks obey the same (8, 128) rule)
POP_BLOCK = 8


def _make_kernel(n_nodes: int, n_i: int, n_o: int):
    def kernel(funcs_ref, in0_ref, in1_ref, outs_ref, planes_ref, o_ref,
               sig_ref):
        w = planes_ref.shape[1]
        sig_ref[0:n_i, :] = planes_ref[...]
        ones = jnp.full((1, w), 0xFFFFFFFF, dtype=jnp.uint32)
        zeros = jnp.zeros((1, w), dtype=jnp.uint32)

        def gate_body(j, _):
            f = funcs_ref[j]
            a = sig_ref[pl.ds(in0_ref[j], 1), :]
            b = sig_ref[pl.ds(in1_ref[j], 1), :]
            r = jax.lax.switch(f, [
                lambda a, b: a,            # identity
                lambda a, b: ~a,           # not
                lambda a, b: a & b,        # and
                lambda a, b: a | b,        # or
                lambda a, b: a ^ b,        # xor
                lambda a, b: ~(a & b),     # nand
                lambda a, b: ~(a | b),     # nor
                lambda a, b: ~(a ^ b),     # xnor
                lambda a, b: zeros,        # const0
                lambda a, b: ones,         # const1
            ], a, b)
            sig_ref[pl.ds(n_i + j, 1), :] = r
            return 0

        jax.lax.fori_loop(0, n_nodes, gate_body, 0)

        def out_body(o, _):
            o_ref[pl.ds(o, 1), :] = sig_ref[pl.ds(outs_ref[o], 1), :]
            return 0

        jax.lax.fori_loop(0, n_o, out_body, 0)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("n_nodes", "n_i", "n_o", "interpret"))
def bitsim_pallas(funcs: jax.Array, in0: jax.Array, in1: jax.Array,
                  outs: jax.Array, planes: jax.Array, *, n_nodes: int,
                  n_i: int, n_o: int, interpret: bool = False) -> jax.Array:
    """Evaluate a netlist on uint32 bit-planes.

    funcs/in0/in1: (n_nodes,) int32; outs: (n_o,) int32 signal indices;
    planes: (n_i, W) uint32.  Returns (n_o, W) uint32.
    """
    w = planes.shape[1]
    pw = (-w) % W_BLOCK
    planes_p = jnp.pad(planes, ((0, 0), (0, pw)))
    wp = planes_p.shape[1]
    grid = (wp // W_BLOCK,)
    out = pl.pallas_call(
        _make_kernel(n_nodes, n_i, n_o),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * 4 + [
            pl.BlockSpec((n_i, W_BLOCK), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n_o, W_BLOCK), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n_o, wp), jnp.uint32),
        scratch_shapes=[
            pltpu.VMEM((n_i + n_nodes, W_BLOCK), jnp.uint32),
        ],
        interpret=interpret,
    )(funcs, in0, in1, outs, planes_p)
    return out[:, :w]


def _make_pop_kernel(n_nodes: int, n_i: int, n_o: int):
    """Population variant of ``_make_kernel``: the netlist refs hold a
    ``POP_BLOCK``-candidate SMEM block; the grid's candidate picks its
    row."""

    def kernel(funcs_ref, in0_ref, in1_ref, outs_ref, planes_ref, o_ref,
               sig_ref):
        q = jax.lax.rem(pl.program_id(0), POP_BLOCK)
        w = planes_ref.shape[1]
        sig_ref[0:n_i, :] = planes_ref[...]
        ones = jnp.full((1, w), 0xFFFFFFFF, dtype=jnp.uint32)
        zeros = jnp.zeros((1, w), dtype=jnp.uint32)

        def gate_body(j, _):
            f = funcs_ref[q, j]
            a = sig_ref[pl.ds(in0_ref[q, j], 1), :]
            b = sig_ref[pl.ds(in1_ref[q, j], 1), :]
            r = jax.lax.switch(f, [
                lambda a, b: a,            # identity
                lambda a, b: ~a,           # not
                lambda a, b: a & b,        # and
                lambda a, b: a | b,        # or
                lambda a, b: a ^ b,        # xor
                lambda a, b: ~(a & b),     # nand
                lambda a, b: ~(a | b),     # nor
                lambda a, b: ~(a ^ b),     # xnor
                lambda a, b: zeros,        # const0
                lambda a, b: ones,         # const1
            ], a, b)
            sig_ref[pl.ds(n_i + j, 1), :] = r
            return 0

        jax.lax.fori_loop(0, n_nodes, gate_body, 0)

        def out_body(o, _):
            o_ref[0, pl.ds(o, 1), :] = sig_ref[pl.ds(outs_ref[q, o], 1), :]
            return 0

        jax.lax.fori_loop(0, n_o, out_body, 0)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("n_nodes", "n_i", "n_o", "interpret"))
def bitsim_pop_pallas(funcs: jax.Array, in0: jax.Array, in1: jax.Array,
                      outs: jax.Array, planes: jax.Array, *, n_nodes: int,
                      n_i: int, n_o: int,
                      interpret: bool = False) -> jax.Array:
    """Evaluate a POPULATION of netlists on shared uint32 bit-planes.

    funcs/in0/in1: (P, n_nodes) int32; outs: (P, n_o) int32;
    planes: (n_i, W) uint32 shared by every candidate.  Returns
    (P, n_o, W) uint32 — row p bit-identical to ``bitsim_pallas`` on
    candidate p's netlist slice.  Netlists of differing node counts are
    stacked by padding with inactive const0 nodes
    (``repro.core.netlist.stack_netlists``), which cannot change any
    output: padded nodes are appended past every referenced index.
    """
    p = funcs.shape[0]
    pp = (-p) % POP_BLOCK
    funcs, in0, in1, outs = (jnp.pad(x, ((0, pp), (0, 0)))
                             for x in (funcs, in0, in1, outs))
    w = planes.shape[1]
    pw = (-w) % W_BLOCK
    planes_p = jnp.pad(planes, ((0, 0), (0, pw)))
    wp = planes_p.shape[1]
    grid = (p + pp, wp // W_BLOCK)

    def netlist_spec(width):
        return pl.BlockSpec((POP_BLOCK, width),
                            lambda q, i: (q // POP_BLOCK, 0),
                            memory_space=pltpu.SMEM)

    out = pl.pallas_call(
        _make_pop_kernel(n_nodes, n_i, n_o),
        grid=grid,
        in_specs=[netlist_spec(n_nodes)] * 3 + [
            netlist_spec(n_o),
            pl.BlockSpec((n_i, W_BLOCK), lambda q, i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, n_o, W_BLOCK), lambda q, i: (q, 0, i)),
        out_shape=jax.ShapeDtypeStruct((p + pp, n_o, wp), jnp.uint32),
        scratch_shapes=[
            pltpu.VMEM((n_i + n_nodes, W_BLOCK), jnp.uint32),
        ],
        interpret=interpret,
    )(funcs, in0, in1, outs, planes_p)
    return out[:p, :, :w]
