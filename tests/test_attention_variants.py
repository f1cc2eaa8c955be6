"""Attention equivalences: flash custom-VJP vs naive autodiff; banded/chunked
static-local variants vs the masked-global oracle; grouped-scan forward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import (chunked_attention, flash_attention,
                                    local_attention, naive_attention)

RNG = np.random.default_rng(0)


def _qkv(B, S, H, KV, hd):
    q = jnp.asarray(RNG.normal(size=(B, S, H, hd)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(B, S, KV, hd)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(B, S, KV, hd)).astype(np.float32))
    return q, k, v


@pytest.mark.parametrize("B,S,H,KV,hd,w,c", [
    (2, 130, 8, 2, 32, 0, 0),
    (1, 257, 4, 4, 16, 0, 0),
    (2, 100, 6, 2, 16, 17, 0),
    (1, 200, 4, 2, 32, 0, 64),
])
def test_flash_fwd_bwd_matches_naive(B, S, H, KV, hd, w, c):
    q, k, v = _qkv(B, S, H, KV, hd)

    def lf(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, window=w,
                                       chunk=c, block_q=64, block_k=32) ** 2)

    def ln(q, k, v):
        return jnp.sum(naive_attention(q, k, v, causal=True, window=w,
                                       chunk=c) ** 2)

    np.testing.assert_allclose(float(lf(q, k, v)), float(ln(q, k, v)),
                               rtol=3e-4)
    gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(ln, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-3, atol=3e-3)


@pytest.mark.parametrize("B,S,H,KV,hd,w", [
    (2, 200, 4, 2, 16, 32),
    (1, 129, 4, 4, 8, 64),     # ragged tail
    (2, 96, 2, 2, 8, 32),
    (1, 64, 2, 2, 8, 64),      # S == w degenerate
])
@pytest.mark.parametrize("impl", ["naive", "flash"])
def test_banded_local_equals_masked_global(B, S, H, KV, hd, w, impl):
    q, k, v = _qkv(B, S, H, KV, hd)
    kw = {"block_q": 32, "block_k": 32} if impl == "flash" else {}
    got = local_attention(q, k, v, window=w, impl=impl, **kw)
    want = naive_attention(q, k, v, causal=True, window=w, chunk=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("B,S,H,KV,hd,c", [
    (2, 200, 4, 2, 16, 32),
    (1, 100, 4, 4, 8, 64),
])
def test_chunked_equals_masked_global(B, S, H, KV, hd, c):
    q, k, v = _qkv(B, S, H, KV, hd)
    got = chunked_attention(q, k, v, chunk=c, impl="naive")
    want = naive_attention(q, k, v, causal=True, window=0, chunk=c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


def test_banded_issues_fewer_flops():
    """The static-local variant must *not issue* out-of-window work."""
    B, S, H, KV, hd, w = 2, 4096, 8, 4, 64, 512
    q = jax.ShapeDtypeStruct((B, S, H, hd), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((B, S, KV, hd), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((B, S, KV, hd), jnp.bfloat16)
    full = jax.jit(lambda q, k, v: naive_attention(q, k, v, causal=True)
                   ).lower(q, k, v).compile().cost_analysis()["flops"]
    band = jax.jit(lambda q, k, v: local_attention(q, k, v, window=w,
                                                   impl="naive")
                   ).lower(q, k, v).compile().cost_analysis()["flops"]
    assert band < full / 3, (band, full)


@pytest.mark.parametrize("name,group", [("hymba-1.5b", 2),
                                        ("llama4-scout-17b-a16e", 2)])
def test_grouped_scan_matches_baseline(name, group):
    from repro.configs import smoke_config
    from repro.models import loss_fn, model_schema, prefill
    from repro.models.layers import init_params
    cfg = smoke_config(name).replace(n_layers=4)
    params = init_params(model_schema(cfg), jax.random.PRNGKey(0),
                         cfg.param_dtype())
    toks = jnp.asarray(RNG.integers(0, cfg.vocab_size, size=(2, 16)),
                       jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    l1 = loss_fn(params, batch, cfg)
    l2 = loss_fn(params, batch, cfg.replace(layer_group=group))
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    p1, c1 = prefill(params, {"tokens": toks}, cfg, cache_seq=24)
    p2, c2 = prefill(params, {"tokens": toks},
                     cfg.replace(layer_group=group), cache_seq=24)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), rtol=2e-3,
                               atol=2e-3)
    for key in c1:
        np.testing.assert_allclose(np.asarray(c1[key], np.float32),
                                   np.asarray(c2[key], np.float32),
                                   rtol=2e-3, atol=2e-3)
