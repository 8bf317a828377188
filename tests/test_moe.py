"""MoE dispatch: virtual-expert equivalence, capacity, load-balance aux;
the held (dropless) path against the plain reference."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import params as pdefs
from repro.models.config import ArchConfig, BlockSpec, FF, Mixer, MoEConfig, uniform_groups
from repro.models.moe import EP_TARGET, capacity, expert_split, moe_apply, moe_defs


def _cfg(e=4, k=2, d=32, f=64, cf=1.75):
    # cf < e / k keeps these on the capacity path; at e=4, k=2 a group of
    # up to 16 tokens still has a slot for each of its assignments
    return ArchConfig(
        name="moe-test",
        family="moe",
        d_model=d,
        n_heads=4,
        n_kv_heads=4,
        d_ff=f,
        vocab_size=128,
        groups=uniform_groups(BlockSpec(Mixer.GLOBAL_ATTN, FF.MOE), 1),
        moe=MoEConfig(n_experts=e, top_k=k, capacity_factor=cf),
        max_seq_len=64,
    )


def _params(cfg, key):
    return pdefs.materialize(moe_defs(cfg), key)


def test_expert_split_values():
    assert expert_split(_cfg(e=16)) == 1
    assert expert_split(_cfg(e=8)) == 2
    assert expert_split(_cfg(e=4)) == 4
    assert expert_split(_cfg(e=2)) == 8


def test_virtual_experts_match_dense_unsplit():
    """The f-sliced virtual experts must compute exactly the same function
    as the unsplit experts: run moe_apply, then re-run with a manually
    merged (e, d, f) weight view through a dense reference."""
    cfg = _cfg(e=4, k=2, d=16, f=32)
    split = expert_split(cfg)  # 4
    key = jax.random.PRNGKey(0)
    p = _params(cfg, key)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16), jnp.float32)

    out, aux = moe_apply(cfg, p, x)

    # dense reference: merge virtual slices back to (e, d, f) and compute
    # every expert for every token, weighted by the same top-k gates
    e, d, f = 4, 16, 32
    wg = p["w_gate"].reshape(e, split, d, f // split).transpose(0, 2, 1, 3).reshape(e, d, f)
    wu = p["w_up"].reshape(e, split, d, f // split).transpose(0, 2, 1, 3).reshape(e, d, f)
    wd = p["w_down"].reshape(e, split, f // split, d)

    xt = x.reshape(-1, d)
    logits = xt @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, 2)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    def expert_out(ei):
        g = xt @ wg[ei]
        u = xt @ wu[ei]
        h = (jax.nn.silu(g) * u)
        # sum over the split f-slices of the down-projection
        hs = h.reshape(-1, split, f // split)
        return sum(hs[:, s_] @ wd[ei, s_] for s_ in range(split))

    all_out = jnp.stack([expert_out(ei) for ei in range(e)], axis=1)
    want = jnp.zeros_like(xt)
    for kk in range(2):
        sel = jnp.take_along_axis(all_out, ids[:, kk][:, None, None], 1)[:, 0]
        want = want + gates[:, kk][:, None] * sel
    want = want.reshape(x.shape)
    # 16 slots an expert for 16 tokens -> nothing dropped; results must
    # match closely
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=2e-2,
                               atol=2e-2)


def test_capacity_drops_overflow():
    """With capacity_factor small, overflowing tokens are dropped (output
    contribution zero) — never NaN."""
    cfg = dataclasses.replace(
        _cfg(e=4, k=2), moe=MoEConfig(n_experts=4, top_k=2,
                                      capacity_factor=0.1),
    )
    p = _params(cfg, jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 32), jnp.float32)
    out, aux = moe_apply(cfg, p, x)
    assert np.isfinite(np.asarray(out)).all()
    # some tokens must produce strictly smaller output than uncapped
    cfg_big = _cfg(e=4, k=2)
    out_big, _ = moe_apply(cfg_big, p, x)
    assert not np.allclose(np.asarray(out), np.asarray(out_big))


def test_capacity_formula():
    cfg = _cfg(e=8, k=2)
    c = capacity(1024, dataclasses.replace(
        cfg, moe=MoEConfig(n_experts=8, top_k=2, capacity_factor=1.25)))
    assert c == 320  # 1024*2*1.25/8 = 320, already a multiple of 8
    assert capacity(4, cfg) == 8  # floor


def test_aux_loss_uniform_router_is_one():
    """With a uniform router, density ~ uniform and aux -> ~1.0 (E * E *
    (1/E) * (1/E)) — the Switch normalization sanity check."""
    cfg = _cfg(e=4, k=2)
    p = _params(cfg, jax.random.PRNGKey(4))
    p = dict(p)
    p["router"] = jnp.zeros_like(p["router"])  # uniform routing
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 32, 32), jnp.float32)
    _, aux = moe_apply(cfg, p, x)
    assert 0.9 < float(aux) < 1.1, float(aux)


def test_group_local_dispatch_matches_global():
    """G groups vs G=1 must give identical outputs when capacity doesn't
    bind (group-locality is a pure partitioning of the dispatch)."""
    cfg = _cfg(e=4, k=2)
    p = _params(cfg, jax.random.PRNGKey(6))
    x = jax.random.normal(jax.random.PRNGKey(7), (4, 8, 32), jnp.float32)

    class Pol:
        moe_groups = 4
        moe_group_ax = None
        moe_token_ax = None
        moe_ep_ax = None
        moe_f_ax = None
        mesh = None

        @staticmethod
        def constrain(t, axes):
            return t

    out_g, _ = moe_apply(cfg, p, x, policy=Pol())
    out_1, _ = moe_apply(cfg, p, x)
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_1),
                               rtol=2e-2, atol=2e-2)


def test_moe_grads_flow():
    cfg = _cfg(e=4, k=2)
    p = _params(cfg, jax.random.PRNGKey(8))
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 8, 32), jnp.float32)

    def loss(p_):
        out, aux = moe_apply(cfg, p_, x)
        return jnp.sum(jnp.square(out)) + 0.01 * aux

    g = jax.grad(loss)(p)
    for name in ("w_gate", "w_up", "w_down", "router"):
        assert float(jnp.max(jnp.abs(g[name]))) > 0.0, name


# ---- the held path ---------------------------------------------------------------

from bench.reference import moe_swiglu, numerics  # noqa: E402
from repro.configs import get_arch, get_smoke  # noqa: E402
from repro.models.moe import dropless, held_apply  # noqa: E402
from repro.models.transformer import LM  # noqa: E402

HELD = get_smoke("mellum2-12b-a2.5b.ep4")  # 4 of 16 experts, top 4
UNCUT = get_smoke("mellum2-12b-a2.5b")  # all 16 held
REF_RUN = {"num_experts_per_tok": 4, "norm_topk_prob": True}
F32 = numerics.of("float32")


def _held_params(cfg, seed=0):
    p = pdefs.materialize(moe_defs(cfg), jax.random.PRNGKey(seed))
    return jax.tree.map(lambda a: a.astype(jnp.float32), p)


def _x(shape=(2, 24, 64), seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _apply(cfg, p, x):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p_, x_: moe_apply(cfg, p_, x_))(p, x)


def test_paths_by_config():
    """The held path wherever k * cf >= E; mixtral and phi3.5 keep the
    capacity path and its virtual-expert leaves."""
    assert dropless(HELD) and dropless(UNCUT)
    assert dropless(get_arch("mellum2-12b-a2.5b.ep4"))
    for name in ("mixtral-8x22b", "phi3.5-moe-42b-a6.6b"):
        for cfg in (get_arch(name), get_smoke(name)):
            assert not dropless(cfg)
            e, s = cfg.moe.n_experts, expert_split(cfg)
            assert moe_defs(cfg)["w_gate"].shape == (
                e * s, cfg.d_model, cfg.d_ff // s)
    defs = moe_defs(get_arch("mellum2-12b-a2.5b.ep4"))
    assert defs["router"].shape == (2304, 64)
    assert defs["router"].dtype == jnp.float32
    assert defs["w_gate"].shape == defs["w_up"].shape == (16, 2304, 896)
    assert defs["w_down"].shape == (16, 896, 2304)


def test_held_layer_matches_the_reference():
    """Experts 0..3 of 16: the reference's share with ``first=0``."""
    p, x = _held_params(HELD), _x()
    out, aux = _apply(HELD, p, x)
    ref, ref_aux = moe_swiglu.apply(p, x, F32, REF_RUN, first=0)
    assert float(jnp.linalg.norm(ref)) > 0
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(aux, ref_aux, rtol=1e-6)


@pytest.mark.parametrize("shares", [4])
def test_shares_add_up_to_the_whole_layer(shares):
    """Four chips, each holding a quarter of the experts, give parts that
    add up to the uncut layer's output; the aux term, over every token and
    expert, is each share's alike and counted once.  Share s holds experts
    4s..4s+3: its router's columns turned so that they come first."""
    whole = _held_params(UNCUT)
    x = _x()
    out, aux = _apply(UNCUT, whole, x)
    ref, ref_aux = moe_swiglu.apply(whole, x, F32, REF_RUN)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    held = 16 // shares
    total = jnp.zeros_like(out)
    for s in range(shares):
        sl = slice(s * held, (s + 1) * held)
        part_p = {"router": jnp.roll(whole["router"], -s * held, axis=1),
                  **{k: whole[k][sl] for k in ("w_gate", "w_up", "w_down")}}
        part, part_aux = _apply(HELD, part_p, x)
        assert float(jnp.linalg.norm(part)) > 0
        np.testing.assert_allclose(part_aux, aux, rtol=1e-5)
        total = total + part
    np.testing.assert_allclose(total, out, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(aux, ref_aux, rtol=1e-6)


def test_held_layer_drops_nothing_where_capacity_does():
    """A router that sends every token's top 4 into the four held experts:
    the held layer computes every assignment, as the reference does; the
    capacity path at cf 1.25 over the same 16 experts drops most of them."""
    p, x = _held_params(HELD), _x()
    x = x.at[..., 0].set(10.0)
    p["router"] = p["router"].at[0, :4].set(5.0).at[0, 4:].set(-5.0)
    _, ids = jax.lax.top_k(x.reshape(-1, 64) @ p["router"], 4)
    assert bool(jnp.all(ids < 4))
    out, _ = _apply(HELD, p, x)
    ref, _ = moe_swiglu.apply(p, x, F32, REF_RUN)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    capped = dataclasses.replace(HELD, moe=MoEConfig(16, 4, 1.25))
    assert not dropless(capped)
    full = dict(p, **{k: jnp.concatenate(
        [p[k], jnp.zeros((12,) + p[k].shape[1:], p[k].dtype)])
        for k in ("w_gate", "w_up", "w_down")})
    dropped, _ = _apply(capped, full, x)
    gap = float(jnp.max(jnp.abs(dropped - ref)) / jnp.max(jnp.abs(ref)))
    assert gap > 0.1, gap


def test_held_gradients_match_the_reference():
    p, x = _held_params(HELD), _x()

    def loss(apply):
        def f(p_, x_):
            out, aux = apply(p_, x_)
            return jnp.sum(jnp.sin(out)) + 0.01 * aux
        return jax.grad(f, argnums=(0, 1))

    with jax.default_matmul_precision("highest"):
        prog = jax.jit(loss(lambda p_, x_: held_apply(HELD, p_, x_)))(p, x)
    ref = loss(lambda p_, x_: moe_swiglu.apply(p_, x_, F32, REF_RUN))(p, x)
    for a, b in zip(jax.tree.leaves(prog), jax.tree.leaves(ref)):
        assert a.shape == b.shape
        assert float(jnp.linalg.norm(b)) > 0
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(jnp.max(jnp.abs(b))))


def test_n_active_params_is_held_aware():
    """k * held / E of the held experts' parameters a token: 8 of 64 routed,
    so an eighth of the 16 held here."""
    lm = LM(get_arch("mellum2-12b-a2.5b.ep4"))
    per_layer = 16 * 3 * 2304 * 896
    assert lm.n_params() - lm.n_active_params() == 28 * per_layer * 7 // 8
    whole = LM(get_arch("mellum2-12b-a2.5b"))
    assert whole.n_params() - whole.n_active_params() == \
        28 * 64 * 3 * 2304 * 896 * 7 // 8
    # the nameplate: 12B parameters, 2.5B active
    assert abs(whole.n_params() - 12e9) < 0.02 * 12e9
    assert abs(whole.n_active_params() - 2.5e9) < 0.05 * 2.5e9
    # the capacity path: top-k of all its experts, as before
    mix = LM(get_arch("mixtral-8x22b"))
    assert mix.n_params() - mix.n_active_params() == \
        56 * 6 * 3 * 6144 * 16_384
