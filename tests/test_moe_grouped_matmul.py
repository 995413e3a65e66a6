"""The sparse experts' gated MLP over rows grouped by expert and the
no-drop layer built on it: the fused Pallas kernel (interpret mode;
compiled for the chip in tests/test_serving.py) against the plain form
(``down(silu(gate) * up)`` from two ``moe_grouped_matmul_ref`` products) and
against a loop over the groups, with empty groups, groups that straddle row
tiles, every row in one group, rows that no group owns and none owned at
all; then ``SparseMoELayer`` against a loop over tokens."""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu
from paddle_tpu import kernels
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed.moe import SparseMoELayer
from paddle_tpu.kernels import moe_grouped_matmul as gmm


def _loop(lhs, gate_up, down, sizes):
    """float64, group by group: down(silu(gate) * up)."""
    lhs, gate_up, down = (np.asarray(a, np.float64)
                          for a in (lhs, gate_up, down))
    i = down.shape[1]
    out = np.zeros((lhs.shape[0], down.shape[2]))
    at = 0
    for g, n in enumerate(sizes):
        h = lhs[at:at + n] @ gate_up[g]
        gate, up = h[:, :i], h[:, i:]
        out[at:at + n] = (gate / (1 + np.exp(-gate)) * up) @ down[g]
        at += n
    return out


def _sizes(kind, groups, rows, rng):
    if kind == "one_row_each":              # decode: a row or two a group
        s = np.zeros(groups, int)
        s[rng.permutation(groups)[:rows]] = 1
    elif kind == "every_other_empty":
        cuts = np.sort(rng.randint(0, rows + 1, groups // 2 - 1))
        s = np.zeros(groups, int)
        s[1::2] = np.diff(np.concatenate([[0], cuts, [rows]]))
    elif kind == "all_in_the_last":
        s = np.zeros(groups, int)
        s[-1] = rows
    elif kind == "rows_nobody_owns":        # experts held elsewhere
        cuts = np.sort(rng.randint(0, rows - 20, groups - 1))
        s = np.diff(np.concatenate([[0], cuts, [rows - 21]]))
    elif kind == "none_owned":              # every row held elsewhere
        s = np.zeros(groups, int)
    else:                                   # "ragged": runs straddle tiles
        cuts = np.sort(rng.randint(0, rows + 1, groups - 1))
        s = np.diff(np.concatenate([[0], cuts, [rows]]))
    return s


@pytest.mark.parametrize("kind", ["ragged", "one_row_each",
                                  "every_other_empty", "all_in_the_last",
                                  "rows_nobody_owns", "none_owned"])
@pytest.mark.parametrize("rows,k,i,groups,dtype,split", [
    (48, 128, 128, 8, "float32", False),      # least row tile (8)
    (40, 128, 128, 64, "bfloat16", False),    # decode tile (16), padded
    (300, 256, 128, 4, "float32", False),     # prefill tile (128), padded
    (200, 128, 128, 4, "bfloat16", False),    # prefill tile (128), padded
    (64, 128, 256, 4, "float32", True),       # intermediate in two tiles
])
def test_kernel_against_the_plain_form_and_a_loop(monkeypatch, kind, rows, k,
                                                  i, groups, dtype, split):
    if split:   # an expert too wide for the budget: cut along I
        monkeypatch.setattr(gmm, "_WEIGHT_BLOCK_BYTES", 3 * k * 128 * 4)
        assert gmm._tiles(rows, k, i, groups, jnp.float32)[1] == i // 2
    rng = np.random.RandomState(rows + len(kind))
    sizes = _sizes(kind, groups, rows, rng)
    lhs = jnp.asarray(rng.randn(rows, k), dtype)
    gate_up = jnp.asarray(rng.randn(groups, k, 2 * i) / np.sqrt(k), dtype)
    down = jnp.asarray(rng.randn(groups, i, k) / np.sqrt(i), dtype)
    gs = jnp.asarray(sizes, jnp.int32)
    want = _loop(lhs, gate_up, down, sizes)
    got = gmm.moe_gated_mlp_pallas(lhs, gate_up, down, gs, interpret=True)
    plain = gmm.moe_gated_mlp_ref(lhs, gate_up, down, gs)
    assert got.dtype == lhs.dtype and got.shape == (rows, k)
    got, plain = (np.asarray(a, np.float64) for a in (got, plain))
    # outputs are of the order of 1: float32 to its rounding, bfloat16 to
    # a few of its steps (2 ** -8) over two contractions
    atol = 1e-4 if dtype == "float32" else 0.05
    np.testing.assert_allclose(got, want, atol=atol)
    np.testing.assert_allclose(plain, want, atol=atol)
    np.testing.assert_allclose(got, plain, atol=atol)
    # rows past the groups' sum come out zero, whatever VMEM held
    assert not got[sizes.sum():].any()


def test_tiles_follow_the_shapes():
    # Laguna-XS.2 (K 2048, I 512): decode, 256 rows over 256 experts, the
    # dtype's least row tile and the expert whole (6 MiB of bf16 weights)
    assert gmm._tiles(256, 2048, 512, 256, jnp.bfloat16) == (16, 512)
    assert gmm._tiles(256, 2048, 512, 256, jnp.float32) == (8, 256)
    # prefill: tens of rows an expert, an MXU-sized tile
    assert gmm._tiles(8192, 2048, 512, 256, jnp.bfloat16) == (128, 512)
    # a wide expert (K 4096, I 14336 = 112 lanes): the widest divisor of
    # the lanes whose three tiles fit 8 MiB
    assert gmm._tiles(4096, 4096, 14336, 8, jnp.bfloat16) == (128, 256)
    # fewer rows than a tile: one tile of them
    assert gmm._tiles(5, 128, 128, 2, jnp.float32) == (8, 128)


def test_visits_name_each_group_with_rows_once_a_tile():
    sizes = jnp.asarray([0, 3, 0, 14, 1, 0, 6, 0], jnp.int32)   # 24 rows
    gid, tile, off, nv = (np.asarray(a) for a in gmm._visits(sizes, 3, 8))
    assert off.tolist() == [0, 0, 3, 3, 17, 18, 18, 24, 24]
    assert int(nv[0]) == 6
    live = list(zip(gid[:6].tolist(), tile[:6].tolist()))
    assert live == [(1, 0), (3, 0), (3, 1), (3, 2), (4, 2), (6, 2)]
    # the rest repeat the last live visit: no new block is asked for
    assert set(zip(gid[6:].tolist(), tile[6:].tolist())) == {(6, 2)}
    assert len(gid) == 3 + 8 - 1


@pytest.mark.parametrize("nt", [1, 3])
def test_the_weight_stream_fetches_each_run_once(nt):
    sizes = jnp.asarray([0, 3, 0, 14, 1, 0, 6, 0], jnp.int32)   # 24 rows
    gid, _, _, nv = gmm._visits(sizes, 3, 8)
    vf, rg, nf = (np.asarray(a) for a in gmm._fetches(gid, nv, nt))
    # live visits (1, 0), (3, 0), (3, 1), (3, 2), (4, 2), (6, 2)
    if nt == 1:     # group 3's three row tiles share one fetch
        assert vf[:6].tolist() == [0, 1, 1, 1, 2, 3]
        assert rg[:4].tolist() == [1, 3, 4, 6] and int(nf[0]) == 4
    else:           # cut into tiles: every step fetches its own
        assert vf[:6].tolist() == [0, 3, 6, 9, 12, 15]
        assert rg[:6].tolist() == [1, 3, 3, 3, 4, 6] and int(nf[0]) == 18
    # fetch f of step (v, t) is vf[v] + t: tile f % nt of rg[f // nt]
    assert all(rg[(vf[v] + t) // nt] == gid[v] for v in range(6)
               for t in range(nt))
    # no rows owned: nothing is fetched
    gid, _, _, nv = gmm._visits(jnp.zeros(8, jnp.int32), 3, 8)
    assert int(gmm._fetches(gid, nv, nt)[2][0]) == 0


def test_policy_takes_the_plain_form_off_the_chip_and_for_odd_widths():
    assert gmm.moe_gated_mlp_impl(128, 128) is gmm.moe_gated_mlp_ref  # CPU
    kernels.set_use_pallas(True)
    try:
        assert gmm.moe_gated_mlp_impl(128, 128) is gmm.moe_gated_mlp_pallas
        assert gmm.moe_gated_mlp_impl(2048, 512) is gmm.moe_gated_mlp_pallas
        assert gmm.moe_gated_mlp_impl(96, 128) is gmm.moe_gated_mlp_ref
        assert gmm.moe_gated_mlp_impl(128, 96) is gmm.moe_gated_mlp_ref
    finally:
        kernels.set_use_pallas(None)


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------

D, DE, E, K = 32, 16, 16, 4


def _silu(a):
    return a / (1 + np.exp(-a))


def _plain_layer(layer, x):
    wr = np.asarray(layer.router.weight._value)
    wgu = np.asarray(layer.gate_up_proj._value)
    wd = np.asarray(layer.down_proj._value)
    xv = np.asarray(x).reshape(-1, D)
    s = 1 / (1 + np.exp(-(xv @ wr)))
    out = np.zeros_like(xv)
    load = np.zeros(E, int)
    for t in range(xv.shape[0]):
        top = np.argsort(-s[t], kind="stable")[:K]
        for e in top:
            g, u = np.split(xv[t] @ wgu[e], 2)
            out[t] += (layer.routed_scaling * s[t, e] / s[t, top].sum()
                       * ((_silu(g) * u) @ wd[e]))
            load[e] += 1
    return out.reshape(x.shape), load


@pytest.fixture(scope="module")
def full():
    paddle_tpu.seed(3)
    return SparseMoELayer(D, DE, E, K, routed_scaling=2.5)


@pytest.fixture
def kernel_if(monkeypatch):
    """``kernel_if(True)``: the layer takes the fused kernel (interpret
    mode), as on the chip, whatever its widths. The kernel has no
    derivative (serving runs it under ``no_grad``), so the layer is called
    without the tape either way."""
    def take(use_kernel):
        if use_kernel:
            monkeypatch.setattr(gmm, "moe_gated_mlp_impl",
                                lambda d_model, d_expert:
                                gmm.moe_gated_mlp_pallas)
    with paddle_tpu.no_grad():
        yield take


@pytest.mark.parametrize("use_kernel", [False, True])
def test_layer_against_a_loop_over_tokens(full, kernel_if, use_kernel):
    x = np.random.RandomState(1).randn(2, 7, D).astype(np.float32)
    kernel_if(use_kernel)
    y, load = full(Tensor(jnp.asarray(x)))
    assert full.fused == use_kernel
    want, want_load = _plain_layer(full, x)
    np.testing.assert_allclose(np.asarray(y._value), want, atol=1e-5)
    assert np.asarray(load._value).tolist() == want_load.tolist()
    assert int(load._value.sum()) == 2 * 7 * K         # no row is dropped


def test_no_row_is_dropped_when_every_token_wants_the_same_experts(full):
    x = np.tile(np.random.RandomState(2).randn(1, 1, D), (1, 40, 1))
    y, load = full(Tensor(jnp.asarray(x, jnp.float32)))
    want, _ = _plain_layer(full, x.astype(np.float32))
    assert sorted(np.asarray(load._value).tolist())[-K:] == [40] * K
    np.testing.assert_allclose(np.asarray(y._value), want, atol=1e-5)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_shares_of_the_experts_add_up_and_the_mask_bounds_the_load(
        full, kernel_if, use_kernel):
    # a share's rows of experts held elsewhere sort into the sentinel group
    kernel_if(use_kernel)
    x = Tensor(jnp.asarray(np.random.RandomState(4).randn(3, 5, D),
                           jnp.float32))
    whole, load = full(x)
    mask = np.zeros((3, 5), bool)
    mask[1] = True
    total, loads = 0, []
    for first in range(0, E, 4):
        share = SparseMoELayer(D, DE, E, K, experts_held=(first, 4),
                               routed_scaling=2.5)
        share.router.weight._value = full.router.weight._value
        share.gate_up_proj._value = full.gate_up_proj._value[first:first + 4]
        share.down_proj._value = full.down_proj._value[first:first + 4]
        y, l = share(x, row_mask=Tensor(jnp.asarray(mask)))
        total = total + y._value
        loads += np.asarray(l._value).tolist()
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole._value),
                               atol=1e-5)
    # the load counts the masked rows alone: 5 tokens' K experts
    assert sum(loads) == 5 * K and int(load._value.sum()) == 15 * K
    _, want_load = _plain_layer(full, np.asarray(x._value)[1:2])
    assert loads == want_load.tolist()


def test_experts_held_must_be_a_range_of_the_experts():
    with pytest.raises(ValueError, match="experts_held"):
        SparseMoELayer(D, DE, E, K, experts_held=(12, 8))


def test_a_shared_expert_is_added_once(full):
    from paddle_tpu import nn

    paddle_tpu.seed(5)
    shared = nn.Linear(D, D, bias_attr=False)
    layer = SparseMoELayer(D, DE, E, K, shared_expert=shared,
                           routed_scaling=2.5)
    for name in ("gate_up_proj", "down_proj"):
        getattr(layer, name)._value = getattr(full, name)._value
    layer.router.weight._value = full.router.weight._value
    x = Tensor(jnp.asarray(np.random.RandomState(6).randn(4, D), jnp.float32))
    y, _ = layer(x)
    np.testing.assert_allclose(
        np.asarray(y._value),
        np.asarray(full(x)[0]._value + shared(x)._value), atol=1e-5)
    assert "shared_expert.weight" in dict(layer.named_parameters())
