"""fabric_reduce — reductions and lane-batched streams as fused Pallas
kernels, plus ``run_dfg``: the capability-gated DFG dispatcher the engine's
pallas backend calls.

Extends the one-shot streaming adaptation (``fabric_stream``, DESIGN.md §2)
to the two kernel classes that previously fell back to the simulator:

  * **accumulator reductions** (running-sum trees from the frontend's
    ``patterns.py``, mac1/mac3/mac2x dot products): the DFG's elementwise
    prologue evaluates on (block_rows, 128) VMEM tiles exactly as in
    ``fabric_stream``; each reduction node then folds its masked operand
    tile *elementwise* into a tile-shaped **carry block** that persists
    across sequential grid steps — the TPU image of the PE's
    immediate-feedback accumulator register (associative ops only — the
    capability matrix keeps SHL/SHR accumulators on the sequential
    simulator). Padding lanes are masked to the op's identity element. The
    last tile -> scalar fold and the node's ``acc_init`` run after the
    kernel, in XLA on the same device: Mosaic lowers elementwise int32 ops
    on tiles, but neither a product or bitwise reduction to a scalar nor a
    scalar store to VMEM.

  * **lane batching** (mirroring PR 4's ``simulate_lanes``): N same-mapping
    requests stack lane-major into one padded grid — lane k owns grid steps
    [k*bpl, (k+1)*bpl) and its own carry blocks, which reset at the lane's
    first step — so one ``pallas_call`` serves a whole config-class batch
    from ``Engine.submit``/``flush``.

Off the accelerator the kernels run under ``interpret=True``
(:func:`default_interpret`); on a TPU they compile via Mosaic.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import dfg as D
from repro.core.isa import AluOp
from repro.engine.capabilities import (CapabilityError, check_backend,
                                       check_stream_length, dfg_features)
from repro.kernels import ref
from repro.kernels.fabric_stream import LANES

I32 = np.int32

# identity element per associative reduction op (padding lanes fold to it)
_IDENTITY = {AluOp.ADD: 0, AluOp.SUB: 0, AluOp.XOR: 0, AluOp.OR: 0,
             AluOp.AND: -1, AluOp.MUL: 1}

# elementwise fold of a reduction's operands (SUB accumulates
# acc - x0 - x1 - ... = acc - sum(x), so its operands fold with ADD)
_FOLD = {AluOp.ADD: jnp.add, AluOp.SUB: jnp.add, AluOp.MUL: jnp.multiply,
         AluOp.AND: jnp.bitwise_and, AluOp.OR: jnp.bitwise_or,
         AluOp.XOR: jnp.bitwise_xor}

# fixed, git-ignored home of JAX's persistent compilation cache inside the
# checkout (a stable path: the directory is part of the cache key)
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def default_interpret() -> bool:
    """The kernels' interpret-mode policy (single source of truth — the
    benchmarks record this in their rows): interpret off the accelerator,
    compile via Mosaic on a TPU."""
    return jax.default_backend() == "cpu"


def use_compile_cache() -> Optional[str]:
    """Keep JAX's persistent compilation cache where
    ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads that variable itself, so
    nothing is set then), else at :data:`COMPILE_CACHE_DIR`. In interpret
    mode the cache stays off. Call before the process's first compile.
    Returns the directory in use, or None."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if default_interpret():
        return None
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir",
                          str(COMPILE_CACHE_DIR))
    return jax.config.jax_compilation_cache_dir


def _output_roles(g: D.DFG) -> Tuple[Dict[str, str], List[str], List[str]]:
    """Classify outputs: reduction-fed (output -> its reduction node, one
    carry block per node) vs full-rate streams (tile blocks)."""
    red_of: Dict[str, str] = {}
    for o in g.outputs:
        e = g.operand(o, "a")
        if g.nodes[e.src].is_reduction():
            red_of[o] = e.src
    full_names = [o for o in g.outputs if o not in red_of]
    red_names = sorted(set(red_of.values()))
    return red_of, full_names, red_names


def _emit_body(g: D.DFG, full_names: List[str], red_names: List[str],
               bpl: int, length: int, block_rows: int):
    """Kernel body: elementwise prologue on the tile, elementwise carry
    folds across grid steps, carry reset at lane boundaries."""
    in_names = list(g.inputs)

    def body(*refs):
        ins = refs[:len(in_names)]
        full_refs = refs[len(in_names):len(in_names) + len(full_names)]
        red_refs = refs[len(in_names) + len(full_names):]
        arrays = {name: r[...] for name, r in zip(in_names, ins)}
        stream_outs, red_ins, _ = ref.eval_dfg_streams(g, arrays)
        for name, r in zip(full_names, full_refs):
            r[...] = stream_outs[name].astype(r.dtype)
        if not red_names:
            return
        i = pl.program_id(0)
        j = jax.lax.rem(i, bpl)            # tile index within this lane
        row = jax.lax.broadcasted_iota(jnp.int32, (block_rows, LANES), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (block_rows, LANES), 1)
        idx = j * (block_rows * LANES) + row * LANES + col
        valid = idx < length               # mask the per-lane padding tail
        for rname, rref in zip(red_names, red_refs):
            op = g.nodes[rname].op
            x = jnp.where(valid, red_ins[rname], _IDENTITY[op])

            @pl.when(j == 0)
            def _(rref=rref, op=op):       # new lane: reset the carry
                rref[...] = jnp.full(rref.shape, _IDENTITY[op], jnp.int32)

            rref[...] = _FOLD[op](rref[...], x)

    return body


def _finish(node: D.Node, carry: jax.Array) -> jax.Array:
    """Fold each lane's ``(n_lanes, tile)`` carry row to a scalar and apply
    the accumulator's initial value (wrapping int32: associativity and
    commutativity let the tile order stand in for the element order)."""
    op = node.op
    part = jax.lax.reduce(carry, jnp.int32(_IDENTITY[op]), _FOLD[op], (1,))
    acc = jnp.int32(node.acc_init)
    return acc - part if op == AluOp.SUB else _FOLD[op](acc, part)


def lanes_call(g: D.DFG, n_lanes: int, length: int, block_rows: int = 8,
               interpret: Optional[bool] = None) -> Callable:
    """Build the lane-batched grid for ``n_lanes`` requests of ``length``
    elements.

    The returned function takes one ``(n_lanes * padded // 128, 128)``
    int32 array per ``g.inputs`` name — each lane's stream zero-padded to
    ``padded`` (whole tiles), lanes stacked in order — and returns the
    full-rate output streams in the same layout, then one ``(n_lanes,)``
    array per reduction node (:func:`_output_roles` order). It needs
    shapes only, so it lowers for a described chip as well."""
    if interpret is None:
        interpret = default_interpret()
    _, full_names, red_names = _output_roles(g)
    tile = block_rows * LANES
    padded = pl.cdiv(length, tile) * tile
    bpl = padded // tile                   # tiles (grid steps) per lane
    block = (block_rows, LANES)
    in_specs = [pl.BlockSpec(block, lambda i: (i, 0)) for _ in g.inputs]
    out_specs = [pl.BlockSpec(block, lambda i: (i, 0)) for _ in full_names]
    # one carry block per reduction node and lane, revisited by every grid
    # step of that lane (sequential TPU grids make the accumulation sound)
    out_specs += [pl.BlockSpec(block, lambda i: (i // bpl, 0))
                  for _ in red_names]
    out_shapes = [jax.ShapeDtypeStruct((n_lanes * padded // LANES, LANES),
                                       jnp.int32) for _ in full_names]
    out_shapes += [jax.ShapeDtypeStruct((n_lanes * block_rows, LANES),
                                        jnp.int32) for _ in red_names]
    call = pl.pallas_call(
        _emit_body(g, full_names, red_names, bpl, length, block_rows),
        grid=(n_lanes * bpl,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )

    def run(*ins2d: jax.Array) -> Tuple[jax.Array, ...]:
        outs = call(*ins2d)
        carries = outs[len(full_names):]
        return tuple(outs[:len(full_names)]) + tuple(
            _finish(g.nodes[r], c.reshape(n_lanes, tile))
            for r, c in zip(red_names, carries))

    return run


def fabric_reduce_lanes(g: D.DFG, inputs_list: List[Dict[str, np.ndarray]],
                        block_rows: int = 8,
                        interpret: Optional[bool] = None
                        ) -> List[Dict[str, np.ndarray]]:
    """Run N same-DFG requests as one lane-batched fused Pallas kernel.

    Handles elementwise chains, select-reducible Branch/Merge conditionals,
    and single-emission reductions; callers gate eligibility through
    :func:`run_dfg_lanes`. Results are bit-exact against the functional
    executor per lane (the 5-way conformance contract).
    """
    out_names = list(g.outputs)
    n_lanes = len(inputs_list)
    lengths = {int(np.asarray(v).shape[0])
               for ins in inputs_list for v in ins.values()}
    if len(lengths) != 1:
        raise CapabilityError(
            f"{g.name}: a lane-batched pallas grid needs equal stream "
            f"lengths across lanes, got {sorted(lengths)}")
    (length,) = lengths
    check_stream_length(g, length)

    if length == 0:
        return [{o: np.zeros(0, dtype=I32) for o in out_names}
                for _ in inputs_list]

    tile = block_rows * LANES
    padded = pl.cdiv(length, tile) * tile

    def stack(name: str) -> jax.Array:
        lanes = []
        for ins in inputs_list:
            x = jnp.asarray(np.asarray(ins[name]), dtype=jnp.int32)
            lanes.append(jnp.pad(x, (0, padded - length)))
        return jnp.concatenate(lanes).reshape(-1, LANES)

    red_of, full_names, red_names = _output_roles(g)
    run = jax.jit(lanes_call(g, n_lanes, length, block_rows, interpret))
    outs = run(*[stack(name) for name in g.inputs])
    full_vals = {name: np.asarray(o).reshape(n_lanes, padded)
                 for name, o in zip(full_names, outs)}
    red_vals = {name: np.asarray(o)
                for name, o in zip(red_names, outs[len(full_names):])}

    results: List[Dict[str, np.ndarray]] = []
    for k in range(n_lanes):
        lane: Dict[str, np.ndarray] = {}
        for o in out_names:
            if o in red_of:
                lane[o] = red_vals[red_of[o]][k:k + 1].astype(I32)
            else:
                v = full_vals[o][k][:length].astype(I32)
                if g.nodes[o].emit_every == 0 and v.size:
                    v = v[-1:]             # OMN stride-0 'last value' mode
                lane[o] = v
        results.append(lane)
    return results


# ---------------------------------------------------------------------------
# the capability-gated dispatcher (what the engine's pallas backend calls)
# ---------------------------------------------------------------------------

def run_dfg_lanes(g: D.DFG, inputs_list: List[Dict[str, np.ndarray]],
                  block_rows: int = 8,
                  interpret: Optional[bool] = None
                  ) -> List[Dict[str, np.ndarray]]:
    """Dispatch N same-DFG requests to the fused Pallas substrate.

    Raises :class:`CapabilityError` naming every feature outside the
    pallas capability set (engine/capabilities.py)."""
    check_backend(dfg_features(g), "pallas", g.name)
    return fabric_reduce_lanes(g, inputs_list, block_rows=block_rows,
                               interpret=interpret)


def run_dfg(g: D.DFG, inputs: Dict[str, np.ndarray],
            block_rows: int = 8,
            interpret: Optional[bool] = None) -> Dict[str, np.ndarray]:
    """Single-request dispatch (one lane)."""
    return run_dfg_lanes(g, [inputs], block_rows=block_rows,
                         interpret=interpret)[0]
