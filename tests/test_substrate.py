"""Substrate tests: data pipeline, optimizer, gradient compression,
checkpointing, fault tolerance, partitioning rules."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:
    from hypothesis_stub import given, settings, st
from jax.sharding import PartitionSpec as P

from repro.checkpoint.ckpt import Checkpointer
from repro.data.pipeline import DataCfg, TokenPipeline
from repro.optim import grad_compress
from repro.optim.adamw import AdamW, clip_by_global_norm, cosine_schedule, wsd_schedule
from repro.runtime import partition as PT
from repro.runtime.fault_tolerance import (HealthMonitor, Heartbeat,
                                           StragglerDetector, elastic_remesh)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_pipeline_deterministic_and_restartable():
    cfg = DataCfg(vocab=1000, seq_len=32, global_batch=8)
    p1 = TokenPipeline(cfg)
    p2 = TokenPipeline(cfg)
    b1, b2 = p1.batch(17), p2.batch(17)
    assert np.array_equal(b1["tokens"], b2["tokens"])
    assert np.array_equal(b1["tokens"][:, 1:], b1["targets"][:, :-1])


def test_pipeline_host_sharding_disjoint_and_complete():
    cfg = DataCfg(vocab=1000, seq_len=16, global_batch=8)
    full = TokenPipeline(cfg, host_id=0, n_hosts=1).batch(3)["tokens"]
    parts = [TokenPipeline(cfg, host_id=h, n_hosts=4).batch(3)["tokens"]
             for h in range(4)]
    rebuilt = np.empty_like(full)
    for h, part in enumerate(parts):
        rebuilt[h::4] = part        # wait: host rows are h + n_hosts*i
    # rows of host h are global rows h, h+4, ...
    for h, part in enumerate(parts):
        for i in range(part.shape[0]):
            assert np.array_equal(part[i], full[h + 4 * i])


# ---------------------------------------------------------------------------
# optimizer + schedules
# ---------------------------------------------------------------------------

def test_adamw_converges_on_quadratic():
    opt = AdamW(lr=lambda s: 0.1, weight_decay=0.0)
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = opt.init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state = opt.update(grads, state, params)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_wsd_schedule_shape():
    lr = wsd_schedule(1.0, warmup=10, stable=50, decay=20)
    assert float(lr(jnp.asarray(5))) == pytest.approx(0.5)
    assert float(lr(jnp.asarray(30))) == pytest.approx(1.0)
    assert float(lr(jnp.asarray(80))) < 0.05          # deep in decay
    cos = cosine_schedule(1.0, warmup=10, total=100)
    assert float(cos(jnp.asarray(100))) == pytest.approx(0.1, abs=0.02)


def test_clip_by_global_norm():
    g = {"a": jnp.asarray([3.0, 4.0])}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(5.0)
    assert float(jnp.linalg.norm(clipped["a"])) == pytest.approx(1.0, abs=1e-5)


# ---------------------------------------------------------------------------
# gradient compression (error feedback)
# ---------------------------------------------------------------------------

def test_grad_compress_roundtrip_bound():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal(5000), jnp.float32)
    err = jnp.zeros(5000, jnp.float32)
    g_hat, new_err = grad_compress.compress_decompress(g, err)
    # per-block error bounded by scale/2 = max|g|/254
    blocks = np.asarray(g).reshape(-1, 1000) if False else None
    assert float(jnp.abs(new_err).max()) <= float(jnp.abs(g).max()) / 254 + 1e-6


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 4000))
def test_property_error_feedback_preserves_signal(n):
    """Over repeated steps with a constant gradient, the error-feedback
    compressor must transmit the true mean (no bias accumulation)."""
    rng = np.random.default_rng(n)
    g = jnp.asarray(rng.standard_normal(n), jnp.float32)
    err = jnp.zeros(n, jnp.float32)
    acc = jnp.zeros(n, jnp.float32)
    steps = 20
    for _ in range(steps):
        g_hat, err = grad_compress.compress_decompress(g, err)
        acc = acc + g_hat
    np.testing.assert_allclose(np.asarray(acc / steps), np.asarray(g),
                               atol=float(jnp.abs(g).max()) / 64 + 1e-5)


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_bf16(tmp_path):
    ck = Checkpointer(str(tmp_path))
    tree = {"params": {"w": jnp.asarray(np.arange(12).reshape(3, 4),
                                        jnp.bfloat16),
                       "b": jnp.asarray([1.5, -2.5], jnp.float32)},
            "step_count": jnp.asarray(7, jnp.int32)}
    ck.save(7, tree, extra={"note": "x"})
    restored, step, extra = ck.restore(tree)
    assert step == 7 and extra["note"] == "x"
    assert restored["params"]["w"].dtype == np.asarray(
        tree["params"]["w"]).dtype
    np.testing.assert_array_equal(np.asarray(restored["params"]["b"]),
                                  np.asarray(tree["params"]["b"]))


def test_checkpoint_gc_and_latest(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    tree = {"x": jnp.zeros(3)}
    for s in (1, 2, 3, 4):
        ck.save(s, tree)
    assert ck.all_steps() == [3, 4]
    assert ck.latest_step() == 4


def test_checkpoint_async(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save_async(5, {"x": jnp.ones(4)})
    ck.wait()
    restored, step, _ = ck.restore({"x": jnp.zeros(4)})
    assert step == 5
    np.testing.assert_array_equal(np.asarray(restored["x"]), np.ones(4))


def test_elastic_remesh_roundtrip(tmp_path):
    """Restore a checkpoint onto a different ('smaller cluster') mesh."""
    from repro.launch.mesh import auto_mesh
    mesh = auto_mesh((1,), ("data",))
    tree = {"w": np.arange(16, dtype=np.float32).reshape(4, 4)}
    out = elastic_remesh(tree, mesh, {"w": P("data", None)})
    np.testing.assert_array_equal(np.asarray(out["w"]), tree["w"])


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------

def test_heartbeat_and_monitor(tmp_path):
    d = str(tmp_path)
    for h in range(3):
        Heartbeat(d, h).beat(step=100)
    Heartbeat(d, 3).beat(step=50)      # lagging host
    mon = HealthMonitor(d, timeout_s=1e9, step_lag=5)
    assert mon.stalled() == [3]


def test_straggler_detector():
    det = StragglerDetector(factor=2.0)
    for s in range(20):
        assert not det.record(s, 1.0)
    assert det.record(20, 5.0)
    assert det.events and det.events[0]["step"] == 20


# ---------------------------------------------------------------------------
# partitioning rules
# ---------------------------------------------------------------------------

def test_fix_spec_repairs_indivisible_dims():
    # granite: 40 experts can't split 16 ways -> EP moves to the FF dim
    spec = PT.fix_spec(P("model", None, None), (40, 1536, 512))
    assert spec == P(None, "model", None)   # largest divisible free dim
    # divisible stays put
    spec = PT.fix_spec(P("model", None, None), (16, 5120, 8192))
    assert spec == P("model", None, None)


def test_zero1_prefers_stack_axis():
    import jax.numpy as jnp
    params = {"layers": {"wq": jax.ShapeDtypeStruct((48, 512, 512),
                                                    jnp.bfloat16)}}
    specs = PT.zero1_specs(params)
    assert specs["layers"]["wq"][0] == "data"


def test_filter_spec_drops_missing_axes():
    assert PT.filter_spec(P(("pod", "data"), None), ("data", "model")) == \
        P(("data",), None)
    assert PT.filter_spec(P("pod", "model"), ("data", "model")) == \
        P(None, "model")
