"""The sparse experts' grouped matrix product and the no-drop layer built
on it: the Pallas kernel (interpret mode; compiled for the chip in
tests/test_serving.py) against its plain form and against a loop over the
groups, with empty groups, groups that end inside a row tile and rows that
no group owns; then ``SparseMoELayer`` against a loop over tokens."""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu
from paddle_tpu import kernels
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed.moe import SparseMoELayer
from paddle_tpu.kernels import moe_grouped_matmul as gmm


def _loop(lhs, rhs, sizes):
    out = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32)
    at = 0
    for g, n in enumerate(sizes):
        out[at:at + n] = (np.asarray(lhs[at:at + n], np.float32)
                          @ np.asarray(rhs[g], np.float32))
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
    else:                                   # "ragged"
        cuts = np.sort(rng.randint(0, rows + 1, groups - 1))
        s = np.diff(np.concatenate([[0], cuts, [rows]]))
    return s


@pytest.mark.parametrize("kind", ["ragged", "one_row_each",
                                  "every_other_empty", "all_in_the_last",
                                  "rows_nobody_owns"])
@pytest.mark.parametrize("rows,k,n,groups,dtype,atol", [
    (48, 128, 256, 8, "float32", 1e-4),       # least row tile (8 rows)
    (40, 128, 128, 64, "bfloat16", 0.15),     # least row tile (16), padded
    (300, 256, 128, 4, "float32", 1e-4),      # MXU row tile (128), padded
])
def test_kernel_against_the_plain_form_and_a_loop(kind, rows, k, n, groups,
                                                  dtype, atol):
    rng = np.random.RandomState(rows + len(kind))
    sizes = _sizes(kind, groups, rows, rng)
    lhs = jnp.asarray(rng.randn(rows, k), dtype)
    rhs = jnp.asarray(rng.randn(groups, k, n), dtype)
    gs = jnp.asarray(sizes, jnp.int32)
    want = _loop(lhs, rhs, sizes)
    got = gmm.moe_grouped_matmul_pallas(lhs, rhs, gs, interpret=True)
    plain = gmm.moe_grouped_matmul_ref(lhs, rhs, gs)
    assert got.dtype == lhs.dtype and got.shape == (rows, n)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=atol)
    np.testing.assert_allclose(np.asarray(plain, np.float32), want, atol=atol)
    # rows past the groups' sum come out zero, whatever VMEM held
    assert not np.asarray(got, np.float32)[sizes.sum():].any()


def test_tiles_follow_the_shapes():
    # decode: 256 rows over 256 experts, the dtype's least tile
    assert gmm._tiles(256, 2048, 1024, 256, jnp.bfloat16) == (16, 512)
    assert gmm._tiles(256, 512, 2048, 256, jnp.bfloat16) == (16, 2048)
    assert gmm._tiles(256, 2048, 1024, 256, jnp.float32) == (8, 256)
    # prefill: tens of rows an expert, an MXU-sized tile
    assert gmm._tiles(8192, 2048, 1024, 256, jnp.bfloat16) == (128, 512)
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


def test_policy_takes_the_plain_form_off_the_chip_and_for_odd_widths(
        monkeypatch):
    calls = []
    monkeypatch.setattr(gmm, "moe_grouped_matmul_pallas",
                        lambda *a, **k: calls.append("pallas"))
    monkeypatch.setattr(gmm, "moe_grouped_matmul_ref",
                        lambda *a, **k: calls.append("plain"))
    lhs, gs = jnp.zeros((4, 128)), jnp.asarray([4, 0], jnp.int32)
    gmm.moe_grouped_matmul(lhs, jnp.zeros((2, 128, 128)), gs)   # CPU
    kernels.set_use_pallas(True)
    try:
        gmm.moe_grouped_matmul(lhs, jnp.zeros((2, 128, 128)), gs)
        gmm.moe_grouped_matmul(lhs[:, :96], jnp.zeros((2, 96, 128)), gs)
    finally:
        kernels.set_use_pallas(None)
    assert calls == ["plain", "pallas", "plain"]


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


@pytest.mark.parametrize("use_kernel", [False, True])
def test_layer_against_a_loop_over_tokens(full, use_kernel):
    x = np.random.RandomState(1).randn(2, 7, D).astype(np.float32)
    kernels.set_use_pallas(True if use_kernel else None)
    try:
        y, load = full(Tensor(jnp.asarray(x)))
    finally:
        kernels.set_use_pallas(None)
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


def test_shares_of_the_experts_add_up_and_the_mask_bounds_the_load(full):
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
