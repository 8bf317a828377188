"""dist.compression: property-based equivalence + conservation tests.

The three invariants that make the compressed exchange safe to ship:

1. dense scheme at v = 0 is bit-compatible with BSP (Corollary 1);
2. topk never exceeds its byte budget;
3. error feedback conserves update mass under EVERY scheme — the
   communicated part plus the new residual always reconstructs r + u.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.dist.compression import (
    CompressionConfig,
    _block_topk_mask,
    apply_combined,
    isp_compressed_step,
    split_significant,
)
from repro.wire import codec as wire_codec

DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


def _pod_tree(seed, n_pods, shape, dtype=jnp.float32, scale=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = (scale * jax.random.normal(ks[0], shape, jnp.float32)).astype(dtype)
    u = (scale * 0.1 * jax.random.normal(
        ks[1], (n_pods,) + shape, jnp.float32)).astype(dtype)
    r = (scale * 0.01 * jax.random.normal(
        ks[2], (n_pods,) + shape, jnp.float32)).astype(dtype)
    return u, x, r


# -- config validation --------------------------------------------------------


def test_config_rejects_bad_values():
    with pytest.raises(ValueError):
        CompressionConfig(scheme="gzip")
    with pytest.raises(ValueError):
        CompressionConfig(scheme="topk", budget=0.0)
    with pytest.raises(ValueError):
        CompressionConfig(scheme="topk", budget=1.5)
    with pytest.raises(ValueError):
        CompressionConfig(block=0)


def test_k_per_block_floor():
    cfg = CompressionConfig(scheme="topk", budget=0.001, block=128)
    assert cfg.k_per_block() == 1  # never zero: progress is guaranteed
    assert CompressionConfig(scheme="topk", budget=1.0).k_per_block() == 128


# -- Corollary 1: dense at v=0 == BSP ----------------------------------------


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(0, 2**16),
    n_pods=st.integers(1, 4),
    n=st.integers(1, 257),
)
def test_dense_v0_equals_bsp(seed, n_pods, n):
    """With v = 0 and zero residual, the dense exchange is exactly the BSP
    all-reduce: combined == sum_p u_p, residual stays zero."""
    cfg = CompressionConfig(scheme="dense")
    u, x, _ = _pod_tree(seed, n_pods, (n,))
    r = jnp.zeros_like(u)
    combined, res2, stats = isp_compressed_step(
        cfg, {"w": u}, {"w": x}, {"w": r}, jnp.float32(0.0)
    )
    np.testing.assert_allclose(
        np.asarray(combined["w"]), np.asarray(jnp.sum(u, axis=0)),
        rtol=1e-6, atol=1e-7,
    )
    assert float(jnp.max(jnp.abs(res2["w"]))) == 0.0
    # and the filter reports full communication
    nz_frac = float(jnp.mean((u != 0).astype(jnp.float32)))
    assert float(stats["sent_fraction"]) == pytest.approx(nz_frac, abs=1e-6)


@pytest.mark.parametrize("dtype", list(DTYPES))
def test_dense_v0_matches_bsp_params_after_apply(dtype):
    """apply_combined(params, dense-v0 exchange) == params + sum_p u_p in
    fp32 accumulation, across dtypes."""
    cfg = CompressionConfig(scheme="dense")
    u, x, _ = _pod_tree(3, 3, (33, 7), DTYPES[dtype])
    r = jnp.zeros_like(u)
    combined, _, _ = isp_compressed_step(
        cfg, {"w": u}, {"w": x}, {"w": r}, jnp.float32(0.0)
    )
    got = apply_combined({"w": x}, combined)["w"]
    want = (
        x.astype(jnp.float32)
        + jnp.sum(u.astype(jnp.float32), axis=0)
    ).astype(DTYPES[dtype])
    # bf16 rounds per-pod inside the exchange; one ulp of slack
    tol = 2e-2 if dtype == "bf16" else 1e-6
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


# -- topk budget --------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(0, 2**16),
    n=st.integers(1, 400),
    budget=st.floats(0.01, 0.5),
    block=st.sampled_from([8, 32, 128]),
)
def test_topk_respects_budget_exactly(seed, n, budget, block):
    """Per pod, the number of communicated entries is exactly bounded by
    n_blocks * k_per_block — the wire budget is a hard guarantee."""
    cfg = CompressionConfig(scheme="topk", budget=budget, block=block)
    n_pods = 2
    u, x, r = _pod_tree(seed, n_pods, (n,))
    combined, res2, _ = isp_compressed_step(
        cfg, {"w": u}, {"w": x}, {"w": r}, jnp.float32(0.0)
    )
    # v=0: everything is significant, so the only filtering is topk; the
    # per-pod sent tensor is (r + u) - res'
    sent = np.asarray(r + u - res2["w"])
    eff_block = min(block, n)
    n_blocks = -(-n // eff_block)
    cap = n_blocks * cfg.k_per_block(eff_block)
    for p in range(n_pods):
        assert int(np.sum(sent[p] != 0)) <= cap


def test_topk_keeps_the_largest_magnitudes():
    cfg = CompressionConfig(scheme="topk", budget=0.25, block=4)
    u = jnp.asarray([[4.0, -0.1, 0.2, -8.0, 0.3, 16.0, -0.4, 0.5]])
    x = jnp.ones((8,))
    r = jnp.zeros((1, 8))
    combined, res2, _ = isp_compressed_step(
        cfg, {"w": u}, {"w": x}, {"w": r}, jnp.float32(0.0)
    )
    # block 0 = [4, -.1, .2, -8] keeps -8; block 1 = [.3, 16, -.4, .5]
    # keeps 16
    np.testing.assert_allclose(
        np.asarray(combined["w"]),
        np.asarray([0.0, 0.0, 0.0, -8.0, 0.0, 16.0, 0.0, 0.0]),
    )
    # everything else fed back into the residual
    np.testing.assert_allclose(
        np.asarray(res2["w"][0]),
        np.asarray([4.0, -0.1, 0.2, 0.0, 0.3, 0.0, -0.4, 0.5]),
    )


def _sorted_topk_mask(sig, cfg):
    """The block top-k mask as a full sort selects it: ``lax.top_k`` over
    the zero-padded (nb, block) flatten, scattered into a zero mask."""
    n = sig.size
    block = min(cfg.block, max(n, 1))
    flat = sig.reshape(-1)
    pad = (-n) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, block)
    _, idx = jax.lax.top_k(jnp.abs(blocks), cfg.k_per_block(block))
    keep = jnp.put_along_axis(
        jnp.zeros_like(blocks, dtype=jnp.bool_), idx, True, axis=-1,
        inplace=False,
    )
    if pad:
        keep = keep & (jnp.arange(flat.shape[0]) < n).reshape(-1, block)
    return keep.reshape(-1)[:n].reshape(sig.shape)


def _tied_slices(seed, n_pods, shape, dtype):
    """Pod slices whose magnitudes tie often: halves, +0 and -0, and a
    leading all-zero block (mixed-sign zeros) in every slice."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    full = (n_pods,) + shape
    x = jnp.round(2.0 * jax.random.normal(k1, full)) / 2.0
    zero = jnp.where(jax.random.bernoulli(k2, 0.5, full), -0.0, 0.0)
    x = jnp.where(jax.random.bernoulli(k3, 0.3, full), zero, x)
    flat = x.reshape(n_pods, -1)
    head = min(128, flat.shape[1])
    flat = flat.at[:, :head].set(zero.reshape(n_pods, -1)[:, :head])
    return flat.reshape(full).astype(dtype)


@pytest.mark.parametrize("budget", [0.01, 0.05], ids=["k1", "k6"])
@pytest.mark.parametrize("n_pods", [1, 4])
@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize(
    "shape",
    [(300,), (3, 100), (60,), (2, 30), (16, 256), (2, 8, 384), (4, 256)],
    ids=["pad", "pad-2d", "small", "small-2d", "tiles", "tiles-3d",
         "last-axis-rows"],
)
def test_block_mask_equals_sorted_top_k(shape, dtype, n_pods, budget):
    """The block mask equals the full-sort top-k mask bit for bit, ties,
    signed zeros and all-zero blocks included, on every path: tiles of
    the last two axes, the padded flatten, a leaf smaller than a block.
    Only k > 1 still sorts."""
    cfg = CompressionConfig(scheme="topk", budget=budget, block=128)
    sig = _tied_slices(len(shape) + sum(shape), n_pods, shape, DTYPES[dtype])
    got = jax.vmap(lambda s: _block_topk_mask(s, cfg))(sig)
    want = jax.vmap(lambda s: _sorted_topk_mask(s, cfg))(sig)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    k = cfg.k_per_block(min(cfg.block, sig[0].size))
    jaxpr = str(jax.make_jaxpr(lambda s: _block_topk_mask(s, cfg))(sig[0]))
    assert ("top_k" in jaxpr) == (k > 1)


@pytest.mark.parametrize("budget,sorts", [(0.01, False), (0.05, True)])
def test_topk_step_sorts_only_when_k_exceeds_one(budget, sorts):
    """At 1% of 128 (k = 1) the compiled exchange holds no ``sort``; at 5%
    (k = 6) it does. The selection is static: only the program shows it."""
    cfg = CompressionConfig(scheme="topk", budget=budget, block=128)
    P = 2
    shapes = {"tiles": (16, 256), "pad": (300,)}
    params = {k: jnp.ones(s, jnp.bfloat16) for k, s in shapes.items()}
    pod = {k: jnp.ones((P,) + s, jnp.bfloat16) for k, s in shapes.items()}
    hlo = jax.jit(
        lambda u, x, r: isp_compressed_step(cfg, u, x, r, jnp.float32(0.5))
    ).lower(pod, params, pod).compile().as_text()
    assert (" sort(" in hlo) == sorts


# -- error-feedback conservation ---------------------------------------------


@settings(max_examples=30, deadline=None)
@given(
    seed=st.integers(0, 2**16),
    n=st.integers(1, 311),
    v=st.floats(0.0, 2.0),
    scheme=st.sampled_from(["dense", "topk", "bitmap"]),
    dtype=st.sampled_from(["f32", "bf16"]),
)
def test_error_feedback_conservation(seed, n, v, scheme, dtype):
    """sent_p + res'_p == r_p + u_p for every pod, scheme, threshold and
    dtype — no update mass is ever created or destroyed, including on odd
    (non-multiple-of-block) shapes."""
    cfg = CompressionConfig(scheme=scheme, budget=0.1, block=32)
    n_pods = 3
    u, x, r = _pod_tree(seed, n_pods, (n,), DTYPES[dtype])
    combined, res2, _ = isp_compressed_step(
        cfg, {"w": u}, {"w": x}, {"w": r}, jnp.float32(v)
    )
    # sum_p sent_p == combined, so sum_p (r+u-res') must equal combined
    want = jnp.sum(
        (r + u - res2["w"]).astype(jnp.float32), axis=0
    )
    tol = 2e-2 if dtype == "bf16" else 1e-5
    np.testing.assert_allclose(
        np.asarray(want), np.asarray(combined["w"], np.float32),
        rtol=tol, atol=tol,
    )
    # per-pod reconstruction: res' + sent == r + u exactly, leaf-wise
    sent = (r + u) - res2["w"]
    np.testing.assert_allclose(
        np.asarray(sent + res2["w"], np.float32),
        np.asarray(r + u, np.float32),
        rtol=tol, atol=tol,
    )


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**16), v=st.floats(0.0, 2.0))
def test_bitmap_is_numerically_dense(seed, v):
    """bitmap is an encoding, not a filter: identical numbers to dense."""
    u, x, r = _pod_tree(seed, 2, (129,))
    outs = {}
    for scheme in ("dense", "bitmap"):
        cfg = CompressionConfig(scheme=scheme)
        outs[scheme] = isp_compressed_step(
            cfg, {"w": u}, {"w": x}, {"w": r}, jnp.float32(v)
        )
    np.testing.assert_array_equal(
        np.asarray(outs["dense"][0]["w"]), np.asarray(outs["bitmap"][0]["w"])
    )
    np.testing.assert_array_equal(
        np.asarray(outs["dense"][1]["w"]), np.asarray(outs["bitmap"][1]["w"])
    )
    # wire model (repro.wire bitmap codec): a ceil(n/8) packed mask per pod
    # + 4B per significant value; cheaper than dense exactly when the
    # filter is actually sparse (the paper's point — a dense update gains
    # nothing from a sparse encoding)
    n_total = u.size
    n_pods, leaf_n = u.shape
    hits = float(outs["bitmap"][2]["sent_fraction"]) * n_total
    want_bytes = n_pods * wire_codec.mask_nbytes(leaf_n) + 4.0 * hits
    assert float(outs["bitmap"][2]["wire_bytes"]) == pytest.approx(
        want_bytes, rel=1e-5
    )
    if hits < n_total * (1 - 1 / 32):
        assert float(outs["bitmap"][2]["wire_bytes"]) < float(
            outs["dense"][2]["wire_bytes"]
        )


def test_multi_leaf_pytree_and_broadcast():
    """Params without the pod axis broadcast against (P, ...) updates for
    arbitrarily nested pytrees."""
    cfg = CompressionConfig(scheme="dense")
    P = 2
    params = {"a": jnp.ones((3, 5)), "nested": {"b": jnp.full((4,), 2.0)}}
    u = jax.tree.map(
        lambda x: jnp.repeat(x[None] * 0.5, P, axis=0), params
    )
    r = jax.tree.map(jnp.zeros_like, u)
    combined, res2, stats = isp_compressed_step(
        cfg, u, params, r, jnp.float32(0.0)
    )
    np.testing.assert_allclose(np.asarray(combined["a"]), 0.5 * P)
    np.testing.assert_allclose(np.asarray(combined["nested"]["b"]), 1.0 * P)
    assert float(stats["sent_fraction"]) == pytest.approx(1.0)


def test_split_significant_fused_matches_reference():
    """The Pallas-kernel split and the jnp split agree on a pod-stacked
    odd-shaped tensor (interpret mode; real TPUs run the same kernel)."""
    u, x, r = _pod_tree(11, 3, (5, 77))
    for v in (0.0, 0.4, 1.5):
        sig_a, res_a = split_significant(u, x, r, jnp.float32(v))
        sig_b, res_b = split_significant(
            u, x, r, jnp.float32(v), fused=True, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(sig_a), np.asarray(sig_b), rtol=1e-6, atol=1e-7
        )
        np.testing.assert_allclose(
            np.asarray(res_a), np.asarray(res_b), rtol=1e-6, atol=1e-7
        )
