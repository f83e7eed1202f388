"""Per-architecture smoke tests (reduced configs, per the assignment) and
KV-cache/decode consistency checks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SHAPES, all_archs, cell_runnable
from repro.models import transformer
from repro.models.api import build_model

KEY = jax.random.PRNGKey(0)
ARCHS = list(all_archs())


def _batch_for(cfg, B=2, S=32, rng=None):
    rng = rng or np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)),
                                   jnp.int32)}
    batch["targets"] = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)),
                                   jnp.int32)
    if cfg.family == "vlm":
        batch["patches"] = jnp.zeros((B, cfg.n_patches, cfg.d_model),
                                     cfg.jdtype)
    if cfg.family == "audio":
        batch["frames"] = jnp.asarray(
            rng.standard_normal((B, cfg.encdec.enc_len, cfg.d_model)) * .02,
            cfg.jdtype)
    return batch


@pytest.mark.parametrize("arch_id", ARCHS)
def test_smoke_train_step(arch_id):
    """One forward/loss on a reduced config: finite loss, correct shapes."""
    cfg = all_archs()[arch_id].reduced()
    api = build_model(cfg)
    params = api.init_params(KEY)
    loss, aux = jax.jit(api.loss)(params, _batch_for(cfg))
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch_id}: loss={loss}"


@pytest.mark.parametrize("arch_id", ARCHS)
def test_smoke_grads_finite(arch_id):
    cfg = all_archs()[arch_id].reduced()
    api = build_model(cfg)
    params = api.init_params(KEY)
    grads = jax.grad(lambda p, b: api.loss(p, b)[0])(params, _batch_for(cfg))
    leaves = jax.tree_util.tree_leaves(grads)
    assert leaves
    assert all(bool(jnp.all(jnp.isfinite(l.astype(jnp.float32))))
               for l in leaves), arch_id


def test_decode_matches_full_forward_dense():
    """Incremental decode through the KV cache must match the full causal
    forward — the cache-correctness test."""
    cfg = all_archs()["yi-9b"].reduced()
    api = build_model(cfg)
    params = api.init_params(KEY)
    rng = np.random.default_rng(1)
    B, S = 2, 12
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
    full_logits, _, _ = transformer.forward(params, cfg, tokens=toks)
    state = transformer.init_caches(cfg, B, S + 2)
    got = []
    for t in range(S):
        logits, state = api.decode_step(params, state, toks[:, t:t + 1],
                                        jnp.asarray(t, jnp.int32))
        got.append(logits)
    got = jnp.stack(got, 1)
    np.testing.assert_allclose(
        np.asarray(got[:, :, :cfg.vocab], np.float32),
        np.asarray(full_logits[:, :, :cfg.vocab], np.float32),
        atol=3e-2, rtol=3e-2)


def test_decode_matches_full_forward_ssm():
    """Mamba2: chunked training scan and step-by-step decode recurrence
    must agree (the SSD dual-form correctness check)."""
    from repro.models import ssm
    cfg = all_archs()["mamba2-1.3b"].reduced()
    api = build_model(cfg)
    params = api.init_params(KEY)
    rng = np.random.default_rng(2)
    B, S = 2, 16     # multiple of the reduced chunk (16)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
    full_logits, _, _ = ssm.lm_forward(params, cfg, toks)
    state = ssm.init_lm_states(cfg, B)
    got = []
    for t in range(S):
        logits, state = api.decode_step(params, state, toks[:, t:t + 1],
                                        jnp.asarray(t, jnp.int32))
        got.append(logits)
    got = jnp.stack(got, 1)
    np.testing.assert_allclose(
        np.asarray(got[:, :, :cfg.vocab], np.float32),
        np.asarray(full_logits[:, :, :cfg.vocab], np.float32),
        atol=5e-2, rtol=5e-2)


def test_vocab_padding_is_masked():
    cfg = all_archs()["whisper-base"].reduced()
    assert cfg.vocab_padded % 256 == 0 and cfg.vocab_padded >= cfg.vocab
    api = build_model(cfg)
    params = api.init_params(KEY)
    from repro.models import encdec
    B = 2
    state = (jnp.zeros((B, cfg.encdec.enc_len, cfg.d_model), cfg.jdtype),
             encdec.init_caches(cfg, B, 4))
    logits, _ = api.decode_step(params, state,
                                jnp.zeros((B, 1), jnp.int32),
                                jnp.zeros((), jnp.int32))
    pad = np.asarray(logits, np.float32)[:, cfg.vocab:]
    if pad.size:
        assert np.all(pad <= -1e29), "padded vocab columns must be masked"


def test_long_500k_cell_rules():
    shapes = SHAPES
    for arch_id, cfg in all_archs().items():
        ok, reason = cell_runnable(cfg, shapes["long_500k"])
        if cfg.family in ("ssm", "hybrid"):
            assert ok, arch_id
        else:
            assert not ok and "quadratic" in reason, arch_id


def test_moe_routing_drops_bounded():
    """MoE layer: outputs finite; aux loss near 1 uniform-ish at init."""
    cfg = all_archs()["granite-moe-3b-a800m"].reduced()
    from repro.models.moe import moe_apply, moe_init
    p = moe_init(KEY, cfg.d_model, cfg.d_ff, cfg.moe, cfg.jdtype)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (2, 16, cfg.d_model)) * 0.1, cfg.jdtype)
    out, aux = moe_apply(p, cfg.moe, cfg.d_ff, x)
    assert out.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
    assert float(aux) > 0


def test_moe_shard_map_matches_gspmd():
    """§Perf A2 equivalence: local-EP shard_map MoE == global-scatter MoE
    (capacity large enough that no tokens drop)."""
    import os
    import jax
    import jax.numpy as jnp
    from repro.configs.base import MoESpec
    from repro.models.moe import moe_apply, moe_init
    if len(jax.devices()) < 4:
        import pytest
        pytest.skip("needs 4 local devices (run under dryrun env)")
    from repro.launch.mesh import auto_mesh
    mesh = auto_mesh((2, 2), ("data", "model"))
    spec = MoESpec(n_experts=4, top_k=2, capacity_factor=8.0)
    p = moe_init(jax.random.PRNGKey(0), 32, 64, spec, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32), jnp.float32)
    with mesh:
        o1, _ = jax.jit(lambda p, x: moe_apply(p, spec, 64, x, "gspmd"))(p, x)
        o2, _ = jax.jit(lambda p, x: moe_apply(p, spec, 64, x,
                                               "shard_map"))(p, x)
    assert float(jnp.abs(o1 - o2).max()) < 1e-4
