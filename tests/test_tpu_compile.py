"""Compile the served pallas grids for a described TPU v5e, without a chip.

Interpret mode (every other pallas test) cannot show what Mosaic refuses:
a scalar store to VMEM, a block shape off the (8, 128) tiling, a reduction
it does not lower. These tests lower the lane-batched grids of
``kernels/fabric_reduce.lanes_call`` with ``interpret=False`` for one chip
of a described ``v5e:2x2`` topology and compile them with the TPU compiler
that ships with jaxlib. Nothing runs, so results are checked elsewhere
(tests/test_pallas_parity.py, tests/test_conformance.py, chip_smoke.py).

The topology is described inside a module fixture only: the TPU library
may be loaded by one process at a time, and every test worker imports this
file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.dfg import DFG
from repro.core.isa import AluOp
from repro.engine import Engine
from repro.kernels.fabric_reduce import lanes_call
from repro.kernels.fabric_stream import LANES
from repro.serve.load import compile_recipe, mix_recipes

TILE = 8 * LANES

# one hand DFG per carry op besides ADD (mac1 covers ADD): a prologue
# product folded by the accumulator, with the op's customary initial value
_CARRY_INIT = {AluOp.MUL: 1, AluOp.AND: -1, AluOp.OR: 0, AluOp.XOR: 0,
               AluOp.SUB: 7}


def _carry_dfg(op: AluOp, length: int) -> DFG:
    b = DFG.build(f"carry_{op.name.lower()}")
    m = b.alu("m", AluOp.MUL, b.inp("x"), b.inp("y"))
    s = b.alu("s", op, m, acc_init=_CARRY_INIT[op], emit_every=length)
    b.out("out0", s)
    return b.done()


def _served_shots(label: str, length: int):
    """The per-shot DFGs the pallas engine dispatches for a served class."""
    art = compile_recipe(Engine(backend="pallas"), label, length,
                         mix_recipes(length, "all"))
    return [shot.dfg for shot in art.plan.shots]


# case -> (stream length, DFG factory)
CASES = {
    "relu": (64, lambda n: _served_shots("relu", n)),
    "moe_gate": (64, lambda n: _served_shots("moe_gate", n)),
    "mac1": (64, lambda n: _served_shots("mac1", n)),
    **{f"carry_{op.name.lower()}": (64, lambda n, op=op: [_carry_dfg(op, n)])
       for op in _CARRY_INIT},
    "swiglu_ms_11008": (11008, lambda n: _served_shots("swiglu_ms", n)),
}
CARRY_CASES = ["mac1"] + [f"carry_{op.name.lower()}" for op in _CARRY_INIT]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache: keep the cache off so the suite stays silent
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _stacked_shapes(g: DFG, n_lanes: int, length: int, sharding=None):
    padded = -(-length // TILE) * TILE
    return [jax.ShapeDtypeStruct((n_lanes * padded // LANES, LANES),
                                 jnp.int32, sharding=sharding)
            for _ in g.inputs]


@pytest.mark.parametrize("n_lanes", [1, 8])
@pytest.mark.parametrize("case", sorted(CASES))
def test_grid_compiles_for_v5e(one_chip, case, n_lanes):
    length, dfgs = CASES[case]
    for g in dfgs(length):
        run = lanes_call(g, n_lanes, length, interpret=False)
        compiled = jax.jit(run).lower(
            *_stacked_shapes(g, n_lanes, length, one_chip)).compile()
        assert "tpu_custom_call" in compiled.as_text(), (case, g.name)


def _primitives(jaxpr) -> set:
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub)
    return names


@pytest.mark.parametrize("case", CARRY_CASES)
def test_kernel_body_folds_carries_elementwise(case):
    """Mosaic lowers elementwise int32 tile ops but not a product or
    bitwise reduction to a scalar: no reduction primitive may sit inside
    the kernel body (the final fold runs after it, in XLA)."""
    length, dfgs = CASES[case]
    (g,) = dfgs(length)
    closed = jax.make_jaxpr(lanes_call(g, 8, length, interpret=False))(
        *_stacked_shapes(g, 8, length))
    calls = [e for e in closed.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    body = _primitives(calls[0].params["jaxpr"])
    assert not {p for p in body if p.startswith("reduce")}, sorted(body)
