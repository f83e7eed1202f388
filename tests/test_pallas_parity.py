"""Pallas backend parity (ISSUE 5): reductions, multi-shot plans, and
lane-batched dispatch run on the fused Pallas substrate (interpret mode)
bit-exact against the sim backend — and every kernel outside the declared
capability set is rejected with a diagnostic *naming* the offending
feature, mirroring the frontend's named-equation errors."""
import numpy as np
import pytest

from repro.core import dfg as D
from repro.core import kernels_lib as K
from repro.core.executor import execute
from repro.core.isa import AluOp, CmpOp
from repro.engine import ArtifactCache, CapabilityError, Engine, dfg_features

rng = np.random.default_rng(7)


def _mem_engine(backend):
    return Engine(backend=backend, cache=ArtifactCache(memory_only=True))


def _inputs(g, length):
    return {name: rng.integers(-60, 60, length).astype(np.int32)
            for name in g.inputs}


# ---------------------------------------------------------------------------
# acceptance: every pallas-capable kernels_lib kernel, bit-exact vs sim
# ---------------------------------------------------------------------------

# every single-shot kernel in kernels_lib inside the pallas capability set:
# the elementwise/conditional one-shots plus every reduction kernel
PALLAS_KERNELS = {
    "fft": lambda n: K.fft_butterfly(),
    "relu": lambda n: K.relu(),
    "mac1": K.mac1,
    "mac3": K.mac3,
    "mac2x": K.mac2x,
    "axpby": lambda n: K.axpby(3, 5),
    "scale": lambda n: K.scale(7),
    "scale_add": lambda n: K.scale_add(4),
    "vadd": lambda n: K.vadd(),
    "conv2d_row3": lambda n: K.conv2d_row3(1, -2, 3),
    "conv2d_row": lambda n: K.conv2d_row(1, -2, 3),
    "outer_row": lambda n: K.outer_row(2, -3),
    "outer_row2": lambda n: K.outer_row2(2, -3, 5, 1),
}


@pytest.mark.parametrize("name", sorted(PALLAS_KERNELS))
def test_kernels_lib_pallas_matches_sim(name):
    length = 16
    g = PALLAS_KERNELS[name](length)
    ins = _inputs(g, length)
    ep, es = _mem_engine("pallas"), _mem_engine("sim")
    got = ep.run(ep.compile(g), dict(ins))
    want = es.run(es.compile(g), dict(ins))
    assert set(got) == set(want)
    for o in want:
        np.testing.assert_array_equal(got[o], want[o], err_msg=o)
    # cycle accounting is backend-independent (timing/value decoupling)
    assert ep.tally.total == es.tally.total


def test_multi_shot_plan_runs_on_pallas():
    """A partitioned (pe_limit-forced) multi-shot plan chains per-shot
    pallas kernels through the IMN/OMN buffer handoff, bit-exact."""
    ep, es = _mem_engine("pallas"), _mem_engine("sim")
    ap = ep.compile(K.axpby(3, 5), pe_limit=1)
    As = es.compile(K.axpby(3, 5), pe_limit=1)
    assert ap.n_shots > 1 and "multi-shot" in ap.features
    x, y = (rng.integers(-100, 100, 48).astype(np.int32) for _ in range(2))
    got = ep.run(ap, {"x": x, "y": y})
    want = es.run(As, {"x": x, "y": y})
    np.testing.assert_array_equal(got["out"], want["out"])
    assert ep.tally.total == es.tally.total


@pytest.mark.parametrize("client", ["gemm", "gesummv", "2mm"])
def test_engine_clients_on_pallas_match_numpy(client):
    """The Table II multi-shot benchmark clients (mac3/mac2x reduction
    shots + epilogues) run whole on the pallas backend."""
    from repro.engine import clients
    eng = _mem_engine("pallas")
    if client == "gemm":
        A = rng.integers(-9, 9, (5, 8)).astype(np.int32)
        B = rng.integers(-9, 9, (8, 7)).astype(np.int32)
        C = rng.integers(-9, 9, (5, 7)).astype(np.int32)
        want = (3 * (A.astype(np.int64) @ B) + 2 * C).astype(np.int32)
        clients.run_gemm(eng, 3, A, B, 2, C)
        np.testing.assert_array_equal(C, want)
    elif client == "gesummv":
        N = 6
        A = rng.integers(-9, 9, (N, N)).astype(np.int32)
        B = rng.integers(-9, 9, (N, N)).astype(np.int32)
        x = rng.integers(-9, 9, N).astype(np.int32)
        y = np.zeros(N, dtype=np.int32)
        clients.run_gesummv(eng, 2, 3, A, B, x, y)
        want = (2 * (A.astype(np.int64) @ x)
                + 3 * (B.astype(np.int64) @ x)).astype(np.int32)
        np.testing.assert_array_equal(y, want)
    else:
        A = rng.integers(-5, 5, (4, 6)).astype(np.int32)
        B = rng.integers(-5, 5, (6, 5)).astype(np.int32)
        C = rng.integers(-5, 5, (5, 4)).astype(np.int32)
        Dm = rng.integers(-5, 5, (4, 4)).astype(np.int32)
        want = (2 * (A.astype(np.int64) @ B @ C) + 3 * Dm).astype(np.int32)
        clients.run_2mm(eng, 2, 3, A, B, C, Dm)
        np.testing.assert_array_equal(Dm, want)
    assert eng.stats.lane_batches > 0     # shot batches rode padded grids


# ---------------------------------------------------------------------------
# lane batching: one padded grid == N per-request dispatches, bit-exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("maker", [lambda: K.mac3(16), K.fft_butterfly])
def test_lane_batched_flush_matches_per_request_run(maker):
    g = maker()
    eng, ref = _mem_engine("pallas"), _mem_engine("pallas")
    art, art_r = eng.compile(g), ref.compile(g)
    batch = [_inputs(g, 16) for _ in range(5)]
    handles = [eng.submit(art, dict(ins)) for ins in batch]
    eng.flush()
    assert eng.stats.lane_batches == 1 and eng.stats.lane_requests == 5
    for h, ins in zip(handles, batch):
        want = ref.run(art_r, dict(ins))
        for o in want:
            np.testing.assert_array_equal(h.result()[o], want[o])
    # executor agreement too (the 5-way contract, spot-checked here)
    for h, ins in zip(handles, batch):
        want = execute(g, ins)
        for o in want:
            np.testing.assert_array_equal(h.result()[o], want[o])


def test_lane_batching_requires_equal_lengths():
    g = K.relu()
    eng = _mem_engine("pallas")
    art = eng.compile(g)
    eng.submit(art, {"x": np.ones(16, np.int32)})
    eng.submit(art, {"x": np.ones(24, np.int32)})
    eng.flush()      # incompatible lengths fall back to two separate grids
    assert eng.stats.lane_batches == 0
    assert eng.stats.requests == 2


# ---------------------------------------------------------------------------
# named capability diagnostics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("maker,feature,fragment", [
    (K.dither, "loop-state", "loop-carried back edge"),
    (K.find2min, "loop-state", "loop-carried back edge"),
    (lambda: K.div_loop(7), "recirculation", "recirculation edge"),
    (K.find2min_brmg, "loop-state", "loop-carried back edge"),
])
def test_rejection_names_feature(maker, feature, fragment):
    g = maker()
    assert feature in dfg_features(g)
    eng = _mem_engine("pallas")
    with pytest.raises(CapabilityError) as ei:
        eng.compile(g)
    msg = str(ei.value)
    assert feature in msg and fragment in msg
    # sim still takes everything
    _mem_engine("sim").compile(g)


def test_rejection_names_segmented_reduction():
    """emit_every that is neither 0 nor the stream length is a dispatch-
    time rejection naming the node (lengths are unknown at DFG compile)."""
    from repro.kernels.fabric_reduce import run_dfg
    g = K.mac1(4)                      # emit_every=4
    ins = {k: np.ones(12, np.int32) for k in g.inputs}
    with pytest.raises(CapabilityError, match=r"'s' emits every 4 tokens"):
        run_dfg(g, ins)


def test_segmented_reduction_fails_at_submit_not_mid_flush():
    """A request the backend cannot run must be refused at submit() with
    the queue untouched — an accepted neighbor request must still execute
    at the next flush (no stranded handles)."""
    eng = _mem_engine("pallas")
    good = eng.compile(K.relu())
    bad = eng.compile(K.mac1(4))       # length unknown at DFG compile
    h1 = eng.submit(good, {"x": np.arange(12, dtype=np.int32)})
    with pytest.raises(CapabilityError, match="emits every 4 tokens"):
        eng.submit(bad, {k: np.ones(12, np.int32) for k in bad.dfg.inputs})
    h2 = eng.submit(good, {"x": np.arange(12, dtype=np.int32) - 6})
    eng.flush()
    for h in (h1, h2):
        assert h.result()["out"].shape == (12,)


def test_rejection_names_interior_reduction():
    b = D.DFG.build("acc_interior")
    x = b.inp("x")
    acc = b.alu("acc", AluOp.ADD, x, acc_init=0, emit_every=0)
    post = b.alu("post", AluOp.MUL, acc, const_b=2)
    b.out("out", post)
    g = b.done()
    assert "reduction-interior" in dfg_features(g)
    with pytest.raises(CapabilityError, match="interior"):
        _mem_engine("pallas").compile(g)


def test_rejection_names_nonassociative_reduction_op():
    b = D.DFG.build("acc_shift")
    x = b.inp("x")
    acc = b.alu("acc", AluOp.SHR, x, acc_init=-1, emit_every=0)
    b.out("out", acc)
    g = b.done()
    assert "reduction-op" in dfg_features(g)
    with pytest.raises(CapabilityError, match="non-associative"):
        _mem_engine("pallas").compile(g)


def test_rejection_names_subrate_output():
    """An unmerged branch leg drained by an OMN is a data-dependent-length
    stream — not expressible as a static pallas output shape."""
    b = D.DFG.build("leg_out")
    x = b.inp("x")
    c = b.cmp("c", CmpOp.GTZ, x)
    br = b.branch("br", x, c)
    t = b.alu("t", AluOp.ADD, br, const_b=1, a_port="t")
    f = b.alu("f", AluOp.SUB, br, const_b=1, a_port="f")
    b.out("out_t", t)
    b.out("out_f", f)
    g = b.done()
    assert "subrate-output" in dfg_features(g)
    with pytest.raises(CapabilityError, match="sub-rate"):
        from repro.kernels.fabric_reduce import run_dfg
        run_dfg(g, {"x": np.arange(-4, 4, dtype=np.int32)})


def test_rejection_names_subrate_reduction():
    """An accumulator paced by a branch leg fires only on arriving tokens;
    a speculative tile-reduce would fold every lane — must reject by name,
    never silently diverge."""
    b = D.DFG.build("leg_acc")
    x = b.inp("x")
    c = b.cmp("c", CmpOp.GTZ, x)
    br = b.branch("br", x, c)
    at = b.alu("at", AluOp.ADD, br, a_port="t", acc_init=0, emit_every=0)
    af = b.alu("af", AluOp.ADD, br, a_port="f", acc_init=0, emit_every=0)
    b.out("out_t", at)
    b.out("out_f", af)
    g = b.done()
    assert "reduction-subrate" in dfg_features(g)
    from repro.kernels.fabric_reduce import run_dfg
    with pytest.raises(CapabilityError, match="sub-rate"):
        run_dfg(g, {"x": np.array([3, -2, 5, -1], np.int32)})


def test_nonreducible_merge_raises_not_silently_selects():
    """A MERGE whose legs are not complementary branch paths (e.g. two
    full-rate streams — an arrival-ordered alternating merge) cannot be
    lowered as a select; the jnp evaluator must raise, not return leg a."""
    import jax.numpy as jnp
    from repro.kernels.ref import eval_dfg_elementwise
    b = D.DFG.build("bad_merge")
    x, y = b.inp("x"), b.inp("y")
    m = b.merge("m", x, y)
    b.out("out", m)
    g = b.done()
    with pytest.raises(ValueError, match="select-reducible"):
        eval_dfg_elementwise(g, {"x": jnp.arange(4), "y": jnp.arange(4)})
    # the capability gate flags it structurally too
    assert "merge-order" in dfg_features(g)


def test_rejection_names_merge_order():
    """A MERGE joining the same-polarity legs of two different branches is
    arrival-ordered, not a select — the gate must reject it at compile
    with the named feature (never a mid-flush ValueError)."""
    b = D.DFG.build("tt_merge")
    x, y = b.inp("x"), b.inp("y")
    cx = b.cmp("cx", CmpOp.GTZ, x)
    cy = b.cmp("cy", CmpOp.GTZ, y)
    brx = b.branch("brx", x, cx)
    bry = b.branch("bry", y, cy)
    m = b.merge("m", brx, bry, a_port="t", b_port="t")
    b.out("out", m)
    g = b.done()
    feats = dfg_features(g)
    assert "merge-order" in feats
    from repro.engine.capabilities import backend_skip_reason
    assert backend_skip_reason(g, 8, "pallas") is not None
    with pytest.raises(CapabilityError, match="arrival-ordered"):
        _mem_engine("pallas").compile(g)


def test_same_predicate_cross_branch_merge_is_reducible():
    """Two branches steered by ONE predicate wire (the find2min_brmg
    schema, acyclic here): their opposite legs ARE complementary — the
    provenance check keys on the predicate wire, not the branch node."""
    b = D.DFG.build("xbranch_merge")
    x, y = b.inp("x"), b.inp("y")
    c = b.cmp("c", CmpOp.GTZ, x)
    brx = b.branch("brx", x, c)
    bry = b.branch("bry", y, c)
    m = b.merge("m", brx, bry, a_port="t", b_port="f")
    b.out("out", m)
    g = b.done()
    assert "merge-order" not in dfg_features(g)
    from repro.kernels.fabric_reduce import run_dfg
    ins = {"x": np.array([3, -2, 5, -1], np.int32),
           "y": np.array([7, 8, 9, 10], np.int32)}
    got = run_dfg(g, ins)
    want = execute(g, ins)
    np.testing.assert_array_equal(got["out"], want["out"])


def test_shared_runner_keeps_backend_isolation():
    """Engines of different backends may share one ShotRunner (the
    multishot helpers do); a pallas dispatch must not leave its value
    substrate bound to the shared runner."""
    from repro.core.executor import execute
    from repro.core.multishot import ShotRunner
    r = ShotRunner()
    ep = Engine(backend="pallas", runner=r,
                cache=ArtifactCache(memory_only=True))
    es = Engine(backend="sim", runner=r,
                cache=ArtifactCache(memory_only=True))
    art = ep.compile(K.relu())
    x = np.arange(-4, 4, dtype=np.int32)
    np.testing.assert_array_equal(ep.run(art, {"x": x})["out"],
                                  np.maximum(x, 0))
    assert r.value_fn is execute
    # the sim engine on the same runner still takes loop-state kernels
    arts = es.compile(K.dither())
    out = es.run(arts, {"x": np.arange(8, dtype=np.int32)})
    assert out["out"].shape == (8,)


def test_mixed_length_request_fails_at_submit():
    """Stream-length disagreement is a submit-time rejection (queue
    untouched), not a mid-flush surprise."""
    eng = _mem_engine("pallas")
    art = eng.compile(K.vadd())
    with pytest.raises(ValueError, match="share a length"):
        eng.submit(art, {"x": np.ones(8, np.int32),
                         "y": np.ones(16, np.int32)})
    assert eng.pending() == 0


def test_missing_input_fails_at_submit():
    eng = _mem_engine("pallas")
    art = eng.compile(K.vadd())
    with pytest.raises(ValueError, match="missing input stream"):
        eng.submit(art, {"x": np.ones(8, np.int32)})
    assert eng.pending() == 0


def test_poisoned_request_does_not_wedge_flush():
    """A request whose execution fails mid-flush is dropped, not
    re-queued: requests behind it survive and a retry flush runs them."""
    eng = _mem_engine("sim")
    art = eng.compile(K.relu())
    h1 = eng.submit(art, {"x": np.arange(8, dtype=np.int32)})
    bad = eng.submit(art, {"x": np.full(8, 99, dtype=np.int32)})
    h2 = eng.submit(art, {"x": np.arange(8, dtype=np.int32) + 1})
    real = eng._value_fn

    def flaky(g, ins):
        if int(ins["x"][0]) == 99:
            raise RuntimeError("injected kernel failure")
        return real(g, ins)

    eng._value_fn = flaky
    with pytest.raises(RuntimeError, match="injected"):
        eng.flush()
    assert not bad._done
    assert eng.pending() == 1            # h2 survived, bad was dropped
    eng.flush()
    np.testing.assert_array_equal(h1.result()["out"],
                                  np.maximum(np.arange(8), 0))
    np.testing.assert_array_equal(h2.result()["out"],
                                  np.maximum(np.arange(8) + 1, 0))


def test_lane_grid_failure_falls_back_to_per_request(monkeypatch):
    """If a lane-batched grid fails as a unit, the flush re-dispatches its
    members individually — innocent lane neighbors are never poisoned."""
    eng = _mem_engine("pallas")
    art = eng.compile(K.relu())
    hs = [eng.submit(art, {"x": np.arange(8, dtype=np.int32) + i})
          for i in range(3)]
    monkeypatch.setattr(eng, "_run_lanes",
                        lambda batch: (_ for _ in ()).throw(
                            RuntimeError("grid failed")))
    assert eng.last_lane_error is None
    eng.flush()
    assert eng.stats.lane_batches == 0
    assert eng.stats.lane_batch_failures == 1
    assert eng.last_lane_error == "RuntimeError: grid failed"
    for i, h in enumerate(hs):
        np.testing.assert_array_equal(h.result()["out"],
                                      np.maximum(np.arange(8) + i, 0))


@pytest.mark.parametrize("op,init", [
    (AluOp.ADD, 5), (AluOp.SUB, 7), (AluOp.MUL, 3), (AluOp.AND, -1),
    (AluOp.OR, 0), (AluOp.XOR, 9)], ids=lambda v: getattr(v, "name", v))
def test_carry_fold_bit_exact_per_op(op, init):
    """Each reduction op folds its masked tiles elementwise into a tile
    carry and finishes (tile -> scalar, then ``acc_init``) after the
    kernel: bit-exact, wrapping int32, vs the executor for every lane of
    a grid whose lanes span a padded second tile."""
    from repro.kernels.fabric_reduce import run_dfg_lanes
    n = 1500
    b = D.DFG.build(f"carry_{op.name.lower()}")
    m = b.alu("m", AluOp.MUL, b.inp("x"), b.inp("y"))
    b.out("out0", b.alu("s", op, m, acc_init=init, emit_every=n))
    g = b.done()
    # odd operands keep a wrapping product from collapsing to zero
    lanes = [{k: rng.integers(-5000, 5000, n).astype(np.int32) | 1
              for k in ("x", "y")} for _ in range(3)]
    for ins, out in zip(lanes, run_dfg_lanes(g, lanes)):
        np.testing.assert_array_equal(out["out0"], execute(g, ins)["out0"])


def test_compile_cache_location(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it and nothing is set.
    Unset: the fixed in-checkout directory on an accelerator, no cache in
    interpret mode."""
    import jax
    from repro.kernels import fabric_reduce as FR
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert FR.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.setattr(FR, "default_interpret", lambda: True)
    assert FR.use_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == was
    monkeypatch.setattr(FR, "default_interpret", lambda: False)
    try:
        assert FR.use_compile_cache() == str(FR.COMPILE_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == \
            str(FR.COMPILE_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert FR.COMPILE_CACHE_DIR.name == ".jax_cache"


def test_engine_rejects_unknown_backend():
    with pytest.raises(ValueError, match="backend"):
        Engine(backend="cuda")


# ---------------------------------------------------------------------------
# capability feature analysis itself
# ---------------------------------------------------------------------------

def test_feature_analysis_on_kernels_lib():
    assert dfg_features(K.relu()) == frozenset({"branch-merge"}) or \
        dfg_features(K.relu()) == frozenset()      # relu is MUX-based
    assert "reduction" in dfg_features(K.mac3(8))
    f2 = dfg_features(K.find2min())
    assert "loop-state" in f2 and "reduction-interior" in f2
    fd = dfg_features(K.div_loop(7))
    assert {"recirculation", "branch-merge"} <= fd


def test_artifact_carries_features():
    eng = _mem_engine("sim")
    art = eng.compile(K.mac3(8))
    assert "reduction" in art.features
    clone = type(art).from_bytes(art.to_bytes())
    assert clone.features == art.features
