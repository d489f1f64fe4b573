"""Per-arch smoke tests: reduced configs, one fwd/train step on CPU,
output shapes + finite values (assignment requirement)."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.models import get_model, make_batch
from repro.models.layers import ParamSpec


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_train_step_smoke(arch, rng):
    cfg = get_config(arch).reduced()
    api = get_model(cfg)
    params = api.init(rng)
    batch = make_batch(rng, cfg, batch=2, seq=32)
    (loss, metrics), grads = jax.value_and_grad(api.loss, has_aux=True)(params, batch)
    assert jnp.isfinite(loss), (arch, loss)
    assert loss.shape == ()
    gnorms = [float(jnp.sum(jnp.abs(g.astype(jnp.float32)))) for g in jax.tree.leaves(grads)]
    assert all(jnp.isfinite(g) for g in gnorms), arch
    assert any(g > 0 for g in gnorms), f"{arch}: all-zero grads"


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_decode_step_smoke(arch, rng):
    cfg = get_config(arch).reduced()
    api = get_model(cfg)
    params = api.init(rng)
    cache = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), api.cache_schema(2, 64),
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )
    tok = jnp.ones((2, 1), jnp.int32)
    logits, new_cache = jax.jit(api.decode_step)(params, tok, cache, jnp.int32(0))
    assert logits.shape == (2, cfg.padded_vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32)))), arch
    # cache structure preserved
    assert jax.tree.structure(new_cache) == jax.tree.structure(cache)


@pytest.mark.parametrize("arch", ["llama3-8b", "rwkv6-1.6b", "zamba2-2.7b"])
def test_decode_matches_forward(arch, rng):
    """Teacher-forcing consistency: feeding tokens through decode_step one at
    a time must reproduce forward()'s next-token logits (fp32)."""
    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32")
    api = get_model(cfg)
    params = api.init(rng)
    T = 8
    toks = jax.random.randint(rng, (1, T), 0, cfg.vocab_size, jnp.int32)
    full_logits = api.forward(params, toks)  # (1, T, V)
    cache = jax.tree.map(
        lambda s: jnp.zeros(s.shape, "float32" if s.dtype != "int32" else s.dtype),
        api.cache_schema(1, 32),
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )
    step = jax.jit(api.decode_step)
    for t in range(T):
        logits, cache = step(params, toks[:, t : t + 1], cache, jnp.int32(t))
        err = jnp.max(jnp.abs(logits[0] - full_logits[0, t]))
        assert err < 2e-2, (arch, t, float(err))


def test_param_counts_match_analytic():
    """Analytic n_params (used by roofline MODEL_FLOPS) tracks the real
    schema within 2%."""
    import math

    from repro.models.layers import param_count

    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        api = get_model(cfg)
        actual = param_count(api.schema)
        analytic = cfg.n_params()
        assert abs(actual - analytic) / actual < 0.02, (
            arch, actual, analytic, analytic / actual)


def test_vocab_padding():
    cfg = get_config("minicpm-2b")
    assert cfg.padded_vocab % 256 == 0 and cfg.padded_vocab >= cfg.vocab_size


@pytest.mark.parametrize("blocks", [3.0, 2.25, 0.25],
                         ids=["whole-blocks", "ragged-tail", "one-short-block"])
@pytest.mark.parametrize("arch", ["qwen2-1.5b", "llama3-8b"])  # tied, untied
def test_chunked_loss_matches_whole_sequence_logits(arch, blocks, rng):
    """``chunked_xent`` over ``blocks`` ``LOSS_CHUNK`` blocks gives the loss
    and gradients of whole-sequence logits, masked labels and a padded last
    block included: the gradients are the ones its forward pass forms.  fp32
    compute, so the only difference is summation order."""
    from repro.models import transformer
    from repro.models.layers import softmax_xent

    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32")
    params = get_model(cfg).init(rng)
    S = int(blocks * transformer.LOSS_CHUNK)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, S, cfg.d_model))
    labels = jax.random.randint(jax.random.PRNGKey(2), (2, S), -1, cfg.vocab_size)

    def whole(p, h):
        logits = transformer.unembed(p, h, cfg)
        return softmax_xent(logits, jnp.maximum(labels, 0), labels >= 0)

    def chunked(p, h):
        return transformer.chunked_xent(p, h, labels, cfg)

    (a, ga), (b, gb) = (jax.value_and_grad(f, argnums=(0, 1))(params, h)
                        for f in (whole, chunked))
    assert jnp.allclose(a, b, rtol=1e-5), (a, b)
    for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        assert jnp.allclose(x, y, rtol=1e-4, atol=1e-6)


def _head_inputs(arch, blocks, vocab=None, dtype="float32"):
    """A reduced ``arch``'s head equation and weight, normed-stand-in ``h``
    over ``blocks`` ``LOSS_CHUNK`` blocks, and labels with masked ones."""
    from repro.models import transformer

    cfg = dataclasses.replace(get_config(arch).reduced(), dtype=dtype)
    if vocab:
        cfg = dataclasses.replace(cfg, vocab_size=vocab)
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    eq, w = transformer._head(params, cfg, jnp.dtype(dtype))
    S = int(blocks * transformer.LOSS_CHUNK)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, S, cfg.d_model), dtype)
    labels = jax.random.randint(jax.random.PRNGKey(2), (2, S), -1, cfg.vocab_size)
    return eq, w, h, labels


@pytest.mark.parametrize("blocks", [4, 5, 2.5],
                         ids=["two-groups", "padded-groups", "ragged-tail"])
@pytest.mark.parametrize("arch", ["qwen2-1.5b", "llama3-8b"])  # tied, untied
def test_grouped_head_gradient_matches_one_contraction(arch, blocks):
    """Where a chip's share of the stacked logit gradients would pass the
    byte budget, the head's gradient is contracted per group of blocks and
    summed in f32: the same loss and gradients as one contraction over every
    position, groups padded with masked positions included."""
    from repro.models import transformer

    eq, w, h, labels = _head_inputs(arch, blocks)
    V = w.shape[eq.split(",")[1].index("v")]
    block_bytes = 2 * transformer.LOSS_CHUNK * V * 4

    def loss(budget):
        def f(h, w):
            total, count = transformer.head_xent(h, w, labels, eq, budget)
            return total / count
        return jax.value_and_grad(f, argnums=(0, 1))(h, w)

    (a, ga), (b, gb) = loss(transformer.LOSS_STASH_BYTES), loss(2 * block_bytes)
    assert jnp.allclose(a, b, rtol=1e-6), (a, b)
    for x, y in zip(ga, gb):
        assert jnp.allclose(x, y, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "llama3-8b"])  # tied, untied
def test_loss_gradient_takes_three_head_matmuls(arch):
    """Differentiated, the lowered loss holds exactly three head-sized
    matmuls (the logits, ``dh`` and one ``dW``) and counts ``fused``: no
    recompute of the logits.  The forward alone holds one and counts
    ``plain``.  A vocabulary of 1000 (padded to 1024) tells the head's
    matmuls apart from the 512-position blocks."""
    import re

    from repro.models import transformer

    eq, w, h, labels = _head_inputs(arch, 3, vocab=1000, dtype="bfloat16")
    V = w.shape[eq.split(",")[1].index("v")]
    assert V not in (transformer.LOSS_CHUNK, h.shape[1], h.shape[2])

    def loss(h, w):
        total, count = transformer.head_xent(h, w, labels, eq)
        return total / count

    for fn, path, n in [(jax.value_and_grad(loss, argnums=(0, 1)), "fused", 3),
                        (loss, "plain", 1)]:
        with transformer.count_loss_paths() as paths:
            text = jax.jit(fn).lower(h, w).as_text()
        dots = re.findall(r"stablehlo\.dot_general .*", text)
        assert sum(f"x{V}x" in d or f"<{V}x" in d for d in dots) == n, dots
        assert dict(paths) == {path: 1}


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "seamless-m4t-large-v2",
                                  "zamba2-2.7b", "rwkv6-1.6b"])
def test_loss_is_xent_of_forward_logits(arch, rng):
    """Every LM family's training loss is the masked cross-entropy of its
    own ``forward`` logits (fp32, so only summation order differs)."""
    from repro.models.layers import softmax_xent

    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32")
    api = get_model(cfg)
    params = api.init(rng)
    batch = make_batch(rng, cfg, batch=2, seq=40)
    labels = batch["labels"].at[:, ::3].set(-1)
    batch = {**batch, "labels": labels}
    args = [batch[k] for k in ("frames", "tokens") if k in batch]
    want = softmax_xent(api.forward(params, *args), jnp.maximum(labels, 0),
                        labels >= 0)
    got, _ = api.loss(params, batch)
    assert jnp.allclose(got, want, rtol=1e-5), (arch, got, want)
