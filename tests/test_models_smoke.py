"""Per-arch REDUCED-config smoke tests: one forward/train step on CPU,
asserting output shapes and no NaNs (assignment requirement)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, get_config
from repro.models import build


def _batch(cfg, key, B=2, S=16):
    kt, kl, kx = jax.random.split(key, 3)
    tokens = jax.random.randint(kt, (B, S), 0, cfg.vocab_size)
    labels = jax.random.randint(kl, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": labels}
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(kx, (B, S, cfg.d_model))
    if cfg.family == "vlm":
        batch["vision"] = jax.random.normal(
            kx, (B, cfg.n_vision_tokens, cfg.d_model))
    if cfg.family == "vla":
        batch = {
            "patches": jax.random.normal(kx, (B, cfg.n_patches, cfg.vit_dim)),
            "tokens": tokens[:, :8],
            "actions": jax.random.normal(
                kx, (B, cfg.action_horizon, cfg.action_dim)),
        }
    return batch


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_forward_loss_no_nan(arch):
    cfg = get_config(arch).reduced()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    loss = model.loss_fn(params, batch, jax.random.PRNGKey(2))
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch} loss not finite"


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_one_train_step(arch):
    from repro.train.optimizer import OptConfig
    from repro.train.train_loop import init_state, make_train_step
    cfg = get_config(arch).reduced()
    model = build(cfg)
    state = init_state(model.init(jax.random.PRNGKey(0)))
    step = jax.jit(make_train_step(model, OptConfig(lr=1e-3)))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    state2, metrics = step(state, batch, jax.random.PRNGKey(2))
    assert int(state2.step) == 1
    assert bool(jnp.isfinite(metrics["loss"]))
    # params actually changed somewhere (single bf16 leaves can underflow
    # a 1e-3 update, so check the whole tree)
    diffs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        state.params, state2.params)
    assert max(jax.tree_util.tree_leaves(diffs)) > 0.0


@pytest.mark.parametrize("arch", ["llama3.2-3b", "deepseek-v2-lite-16b",
                                  "mamba2-1.3b", "zamba2-1.2b",
                                  "seamless-m4t-large-v2",
                                  "llama-3.2-vision-11b"])
def test_prefill_decode_shapes(arch):
    cfg = get_config(arch).reduced()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1), B=2, S=8)
    batch.pop("labels", None)
    logits, cache = model.prefill(params, batch)
    assert logits.shape[:2] == (2, 1)
    from repro.runtime.kvcache import pad_cache
    cache = pad_cache(cache, model.cache_specs(2, 16, src_len=8))
    l2, cache = model.decode(params, cache, batch["tokens"][:, :1],
                             jnp.int32(8))
    assert l2.shape[:2] == (2, 1)
    assert bool(jnp.all(jnp.isfinite(l2.astype(jnp.float32))))


def test_init_params_draws_stacked_leaves_per_layer():
    """Stacked leaves are filled one layer at a time in their target
    dtype: every layer gets its own draw at the fan-in scale of the
    whole leaf."""
    from repro.models.sharding import init_params, spec
    specs = {"w": spec((3, 64, 32), ("layers", "d_model", "ff")),
             "n": spec((3, 64), ("layers", "d_model"), init="ones"),
             "e": spec((100, 64), ("vocab", "d_model"), scale=1.0)}
    p = init_params(specs, jax.random.PRNGKey(0))
    w = p["w"].astype(jnp.float32)
    assert p["w"].dtype == jnp.bfloat16 and p["e"].dtype == jnp.bfloat16
    assert not bool(jnp.all(w[0] == w[1]))
    for i in range(3):
        assert abs(float(jnp.std(w[i])) - 64 ** -0.5) < 0.2 * 64 ** -0.5
    assert bool(jnp.all(p["n"] == 1))
    assert abs(float(jnp.std(p["e"].astype(jnp.float32))) - 1.0) < 0.1
