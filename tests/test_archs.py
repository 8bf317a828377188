"""Per-arch smoke tests: every assigned architecture, reduced config,
one train step + prefill + decode on CPU — shapes and finiteness.

The FULL configs are exercised only via the allocation-free dry-run
(launch/dryrun.py); these tests prove the model math of each family.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.configs import ARCH_NAMES, get_arch, get_smoke
from repro.launch.specs import opt_state_defs
from repro.launch.train import make_step
from repro.models import params as pdefs
from repro.models.transformer import LM

B, S, MAX_LEN = 2, 32, 64


def _opt_state(lm):
    o_defs = opt_state_defs(lm.param_defs())
    return jax.tree.map(
        lambda d: jnp.zeros(d.shape, d.dtype)
        if d.init == "zeros"
        else jnp.ones(d.shape, d.dtype),
        o_defs,
        is_leaf=pdefs.is_def,
    )


def _batch(cfg, b=B, s=S, train=True):
    batch = {"tokens": jnp.ones((b, s), jnp.int32)}
    if train:
        batch["labels"] = jnp.ones((b, s), jnp.int32)
    if cfg.family == "audio":
        batch["frames"] = jnp.ones(
            (b, cfg.encoder.ctx_len, cfg.d_model), jnp.float32
        )
    if cfg.family == "vlm":
        batch["vision_embeds"] = jnp.ones(
            (b, cfg.encoder.ctx_len, cfg.d_model), jnp.float32
        )
    return batch


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_full_config_validates(name):
    cfg = get_arch(name)
    cfg.validate()
    assert cfg.n_layers > 0
    assert cfg.padded_vocab % 256 == 0
    assert cfg.padded_vocab >= cfg.vocab_size


# the assignment's exact full-size numbers
_EXPECT = {
    "whisper-base": dict(L=12, d=512, H=8, kv=8, ff=2048, V=51_865),
    "phi4-mini-3.8b": dict(L=32, d=3072, H=24, kv=8, ff=8192, V=200_064),
    "gemma3-12b": dict(L=48, d=3840, H=16, kv=8, ff=15_360, V=262_144),
    "qwen1.5-32b": dict(L=64, d=5120, H=40, kv=40, ff=27_392, V=152_064),
    "starcoder2-7b": dict(L=32, d=4608, H=36, kv=4, ff=18_432, V=49_152),
    "mixtral-8x22b": dict(L=56, d=6144, H=48, kv=8, ff=16_384, V=32_768),
    "phi3.5-moe-42b-a6.6b": dict(L=32, d=4096, H=32, kv=8, ff=6400,
                                 V=32_064),
    "recurrentgemma-9b": dict(L=38, d=4096, H=16, kv=1, ff=12_288,
                              V=256_000),
    "xlstm-1.3b": dict(L=48, d=2048, H=4, kv=4, ff=0, V=50_304),
    "paligemma-3b": dict(L=18, d=2048, H=8, kv=1, ff=16_384, V=257_216),
    # ff: the experts' width
    "mellum2-12b-a2.5b": dict(L=28, d=2304, H=32, kv=4, ff=896, V=98_304),
    "mellum2-12b-a2.5b.ep4": dict(L=28, d=2304, H=32, kv=4, ff=896,
                                  V=98_304),
}


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_full_config_matches_assignment(name):
    cfg = get_arch(name)
    e = _EXPECT[name]
    assert cfg.n_layers == e["L"], (cfg.n_layers, e["L"])
    assert cfg.d_model == e["d"]
    assert cfg.n_heads == e["H"]
    assert cfg.n_kv_heads == e["kv"]
    assert cfg.d_ff == e["ff"]
    assert cfg.vocab_size == e["V"]


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_smoke_train_step(name):
    cfg = get_smoke(name)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    _, metrics = jax.jit(lm.train_loss)(params, batch)
    assert np.isfinite(float(metrics["xent"]))
    d0 = np.asarray(jax.tree.leaves(params)[0], np.float32)
    # the step donates params and optimizer state
    step = make_step(lm, optim.make("adam", 1e-3), None)
    p2, o2, _, loss, _ = step(params, _opt_state(lm), None, batch)
    assert np.isfinite(float(loss)), name
    # params actually changed
    d1 = np.asarray(jax.tree.leaves(p2)[0], np.float32)
    assert not np.allclose(d0, d1)


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_smoke_prefill_decode(name):
    cfg = get_smoke(name)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    cache = lm.init_cache(B, MAX_LEN)
    logits, cache = jax.jit(lm.prefill)(params, cache, _batch(cfg, s=16,
                                                              train=False))
    assert logits.shape == (B, 1, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all(), name
    dbatch = {"tokens": jnp.ones((B, 1), jnp.int32)}
    if cfg.family == "audio":
        dbatch["frames"] = _batch(cfg, train=False)["frames"]
    logits2, cache = jax.jit(lm.decode_step)(
        params, cache, dbatch, jnp.asarray(16, jnp.int32)
    )
    assert logits2.shape == (B, 1, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits2, np.float32)).all(), name


@pytest.mark.parametrize("name", ["phi4-mini-3.8b", "xlstm-1.3b",
                                  "recurrentgemma-9b", "gemma3-12b"])
def test_decode_matches_prefill_logits(name):
    """Prefill(t0..tn) then decode(t_{n+1}) must equal prefill(t0..t_{n+1})
    for the last position — the KV-cache correctness invariant."""
    cfg = get_smoke(name)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(1))
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, 17), 0,
                              cfg.vocab_size)

    # one-shot prefill over all 17 tokens
    cache_a = lm.init_cache(B, MAX_LEN)
    logits_a, _ = jax.jit(lm.prefill)(
        params, cache_a, {"tokens": toks}
    )

    # prefill 16 then decode the 17th
    cache_b = lm.init_cache(B, MAX_LEN)
    _, cache_b = jax.jit(lm.prefill)(params, cache_b,
                                     {"tokens": toks[:, :16]})
    logits_b, _ = jax.jit(lm.decode_step)(
        params, cache_b, {"tokens": toks[:, 16:17]},
        jnp.asarray(16, jnp.int32),
    )
    np.testing.assert_allclose(
        np.asarray(logits_a[:, -1], np.float32),
        np.asarray(logits_b[:, -1], np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_loss_decreases_when_training():
    """A few steps on structured synthetic tokens must reduce loss."""
    from repro.data.tokens import TokenPipeline
    from repro.launch.train import LM_8M

    lm = LM(LM_8M)
    params = lm.init(jax.random.PRNGKey(0))
    step = make_step(lm, optim.make("adam", 1e-3), None)
    opt = _opt_state(lm)
    pipe = TokenPipeline(LM_8M.vocab_size, 128, 8, seed=0)
    losses = []
    for i in range(30):
        params, opt, _, loss, _ = step(params, opt, None, pipe.next_batch(i))
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.3, (losses[0], losses[-1])


def test_moe_aux_loss_nonzero():
    cfg = get_smoke("mixtral-8x22b")
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    loss, metrics = jax.jit(lm.train_loss)(
        params, {"tokens": toks, "labels": toks}
    )
    assert float(metrics["moe_aux"]) > 0.0


def test_param_counts_near_nameplate():
    """Full configs should land near their nameplate parameter counts."""
    targets = {
        "phi4-mini-3.8b": (3.8e9, 0.25),
        "gemma3-12b": (12e9, 0.25),
        "qwen1.5-32b": (32e9, 0.25),
        "starcoder2-7b": (7e9, 0.30),
        "mixtral-8x22b": (141e9, 0.25),
        "xlstm-1.3b": (1.3e9, 0.30),
    }
    for name, (want, tol) in targets.items():
        lm = LM(get_arch(name))
        n = lm.n_params()
        assert abs(n - want) / want < tol, f"{name}: {n:,} vs {want:,}"


@pytest.mark.parametrize("window", [None, 24])
@pytest.mark.parametrize("core", ["chunked", "kv_chunked", "flash", "flash-g4"])
def test_online_softmax_cores_match_dense(core, window, monkeypatch):
    """The chunked cores (online softmax over KV chunks, no gradient through
    the running max) and the flash kernel (interpreted here, blocks of 128
    so that a row spans three) give the dense core's output and gradients,
    causal and windowed, with chunks small enough for several KV steps."""
    from repro.models import attention as att

    b, s, kh, g, dh, chunk = 2, 64, 2, 2, 16, 16
    if core.startswith("flash"):
        # the kernel's head dim and blocks are whole lane tiles
        monkeypatch.setattr(att, "_flash_block", lambda s_, w_: 128)
        s, dh, g = 384, 128, (4 if core == "flash-g4" else 2)
    kq, kk, kv, kc = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(kq, (b, s, kh, g, dh), jnp.float32)
    k = jax.random.normal(kk, (b, s, kh, dh), jnp.float32)
    v = jax.random.normal(kv, (b, s, kh, dh), jnp.float32)
    cot = jax.random.normal(kc, (b, s, kh, g, dh), jnp.float32)
    pos = jnp.arange(s)
    mask = att._mask(pos, pos, True, window, None)
    fns = {"chunked": att._chunked_core, "kv_chunked": att._kv_chunked_core}

    def dense(q_, k_, v_):
        return jnp.sum(att._dense_core(q_, k_, v_, mask) * cot)

    def chunked(q_, k_, v_):
        if core.startswith("flash"):
            out = att._flash_core(q_, k_, v_, window)
        else:
            out = fns[core](q_, k_, v_, 0, True, window, None, chunk)
        return jnp.sum(out * cot)

    want, want_g = jax.value_and_grad(dense, argnums=(0, 1, 2))(q, k, v)
    got, got_g = jax.value_and_grad(chunked, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for a, e in zip(got_g, want_g):
        np.testing.assert_allclose(a, e, rtol=1e-4, atol=1e-5)


# The two long-sequence cells' attention calls: (arch, tokens a row, window)
_FLASH_CALLS = {
    "phi4mini-16k": ("phi4-mini-3.8b", 16384, None),
    "mellum2-8k-full": ("mellum2-12b-a2.5b.ep4", 8192, None),
    "mellum2-8k-window": ("mellum2-12b-a2.5b.ep4", 8192, 1024),
}


def _call(name, **over):
    arch, s, window = _FLASH_CALLS[name]
    kw = dict(backend="tpu", s=s, head_dim=get_arch(arch).resolved_head_dim,
              window=window, causal=True, prefix_len=None,
              seq_sharded=False, n_devices=1)
    kw.update(over)
    return kw


@pytest.mark.parametrize("case,over,want", [
    ("phi4mini-16k", {}, "flash"),
    ("mellum2-8k-full", {}, "flash"),
    ("mellum2-8k-window", {}, "flash"),
    ("phi4mini-16k", {"s": 2048}, "dense"),
    ("mellum2-8k-window", {"s": 2048}, "banded"),
    ("phi4mini-16k", {"prefix_len": 256}, "chunked"),
    ("mellum2-8k-full", {"s": 2048}, "dense"),
    ("phi4mini-16k", {"causal": False}, "chunked"),
    ("mellum2-8k-window", {"prefix_len": 256}, "banded"),
    ("phi4mini-16k", {"n_devices": 4}, "chunked"),
    ("mellum2-8k-full", {"n_devices": 4}, "chunked"),
    ("mellum2-8k-window", {"n_devices": 4}, "banded"),
    ("phi4mini-16k", {"n_devices": 4, "seq_sharded": True}, "kv_chunked"),
    ("phi4mini-16k", {"backend": "cpu"}, "chunked"),
    ("mellum2-8k-window", {"backend": "cpu"}, "banded"),
    ("phi4mini-16k", {"head_dim": 64}, "chunked"),
    ("phi4mini-16k", {"s": 16384 + 128}, "chunked"),
])
def test_pick_core_takes_flash_only_where_it_applies(case, over, want):
    """The kernel for both long-sequence cells' attention on one TPU; the
    present core for short sequences, prefix-LM, non-causal, a partitioned
    mesh, the CPU, and shapes that are not whole blocks."""
    from repro.models.attention import pick_core

    assert pick_core(**_call(case, **over)) == want


@pytest.mark.parametrize("mode,backend", [
    ("train", "tpu"), ("train", "cpu"), ("decode", "tpu"), ("cross", "tpu")])
def test_attn_apply_runs_the_core_it_picks(monkeypatch, mode, backend):
    """A phi4mini layer at 2 x 16,384 traces to the Pallas kernel where the
    backend is a TPU, and to the XLA scans elsewhere; decode against a
    16,384-token cache and cross-attention from 16,384 tokens keep their
    XLA cores on a TPU too (traced, not run)."""
    from repro.models import attention as att
    from repro.models.config import BlockSpec, FF, Mixer

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = get_arch("phi4-mini-3.8b")
    spec = BlockSpec(Mixer.GLOBAL_ATTN, FF.SWIGLU,
                     rope_base=None if mode == "cross" else 1e4)
    p = jax.tree.map(lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype),
                     att.attn_defs(cfg), is_leaf=pdefs.is_def)
    b, s = 2, 16384
    kv = cfg.n_kv_heads * cfg.resolved_head_dim
    x = jax.ShapeDtypeStruct((b, 1 if mode == "decode" else s, cfg.d_model),
                             jnp.bfloat16)
    cache = ({"k": jax.ShapeDtypeStruct((b, s, kv), jnp.bfloat16),
              "v": jax.ShapeDtypeStruct((b, s, kv), jnp.bfloat16)}
             if mode == "decode" else None)
    enc = (jax.ShapeDtypeStruct((b, 1500, cfg.d_model), jnp.bfloat16)
           if mode == "cross" else None)

    def loss(p_, x_, cache_, enc_):
        y, _ = att.attn_apply(
            cfg, spec, p_, x_, policy=att.ShardingPolicy(), cache=cache_,
            decode_pos=jnp.int32(s - 1) if mode == "decode" else None,
            cross_kv=enc_)
        return jnp.sum(y.astype(jnp.float32))

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(p, x, cache, enc))
    assert ("pallas_call" in text) == (mode == "train" and backend == "tpu")
    if backend == "cpu" or mode == "cross":
        assert "scan" in text
