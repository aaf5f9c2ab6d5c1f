"""K-wide state written back from the sorted batch's segment ends.

``apply_aggregators`` reads each group's last scanned value at the group's
segment end; ``_per_key_layout`` reads each key's row count off the same
boundaries. Both used to scatter the whole batch into the K-wide state
(non-landing rows masked to a drop index). The old write-back is kept
HERE as the reference: state and outputs must be bit-equal to it.
"""

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from siddhi_tpu.ops import aggregators as A
from siddhi_tpu.ops.expressions import TYPE_KEY, VALID_KEY
from siddhi_tpu.ops.keyed_windows import _per_key_layout
from siddhi_tpu.query_api.definitions import AttrType

CTX = {"xp": jnp}


def _old_apply_aggregators(specs, state, cols, ctx, num_keys):
    """``apply_aggregators`` as it was before the segment-end write-back:
    the same sort and scan, then ``base.at[:, scatter_idx].set(scanned)``
    over all B rows with every non-landing row sent to ``num_keys``."""
    gk, valid, types = cols["__gk__"], cols[VALID_KEY], cols[TYPE_KEY]
    B = gk.shape[0]
    participates = valid & ((types == A.CURRENT) | (types == A.EXPIRED))
    is_reset = valid & (types == A.RESET)
    any_reset = jnp.any(is_reset)
    sort_gk = jnp.where(participates | is_reset, gk, num_keys).astype(jnp.int32)
    sort_gk = jnp.where(is_reset, num_keys, sort_gk)
    order = jnp.argsort(sort_gk, stable=True)
    inv_order = jnp.argsort(order, stable=True)
    gk_sorted = sort_gk[order]
    epoch = jnp.cumsum(is_reset.astype(jnp.int32))
    epoch_sorted = (epoch - is_reset.astype(jnp.int32))[order]
    final_epoch = epoch[B - 1]
    same_group = jnp.concatenate([jnp.zeros(1, bool), gk_sorted[1:] == gk_sorted[:-1]])
    same_epoch = jnp.concatenate([jnp.zeros(1, bool), epoch_sorted[1:] == epoch_sorted[:-1]])
    blocked = ~(same_group & same_epoch)
    fold_state = blocked & (epoch_sorted == 0) & (gk_sorted < num_keys)
    last_of_group = jnp.concatenate([gk_sorted[1:] != gk_sorted[:-1], jnp.ones(1, bool)])
    in_final_epoch = epoch_sorted == final_epoch

    new_state, cols = dict(state), dict(cols)
    for i, spec in enumerate(specs):
        st = state[f"a{i}"]
        deltas_sorted = A._deltas(spec, cols, ctx, jnp)[:, order]
        comb = A._combine(spec.kind)
        safe_gk = jnp.minimum(gk_sorted, num_keys - 1)
        folded = comb(st[:, safe_gk].T, deltas_sorted.T).T
        vals = jnp.where(fold_state[None, :], folded, deltas_sorted)

        def scan_op(a, b, comb=comb):
            (ab, av), (bb, bv) = a, b
            return ab | bb, jnp.where(bb[:, None], bv, comb(av, bv))

        _, scanned_bs = lax.associative_scan(scan_op, (blocked, vals.T), axis=0)
        scanned = scanned_bs.T
        out = scanned[:, inv_order]
        idents = jnp.asarray(A._slot_identities(spec.kind, np.dtype(st.dtype)))
        base = jnp.where(
            any_reset,
            jnp.broadcast_to(idents[:, None], st.shape).astype(st.dtype), st)
        upd_mask = last_of_group & in_final_epoch & (gk_sorted < num_keys)
        scatter_idx = jnp.where(upd_mask, gk_sorted, num_keys)
        new_state[f"a{i}"] = base.at[:, scatter_idx].set(scanned, mode="drop")
        value, null_mask = A._output(
            spec, [out[s] for s in range(spec.slots)], ctx)
        cols[spec.out_key] = value.astype(A.T.dtype_of(spec.out_type))
        if null_mask is not None:
            cols[spec.out_key + "?"] = null_mask
    return new_state, cols


def _spec(kind, arg_type, i):
    return A.AggSpec(
        kind=kind,
        arg_fn=None if kind == "count" else (lambda c, _ctx: (c["v"], c["v?"])),
        arg_type=None if kind == "count" else arg_type,
        out_key=f"__agg{i}__", out_type=A.agg_result_type(kind, arg_type))


# every scan-path aggregate, wide (64-bit) and narrow (32-bit) state
_SPECS = [
    ("sum", AttrType.LONG), ("sum", AttrType.DOUBLE), ("avg", AttrType.FLOAT),
    ("min", AttrType.INT), ("max", AttrType.DOUBLE), ("count", None),
    ("stddev", AttrType.DOUBLE), ("maxforever", AttrType.FLOAT),
]


def _batch(case, K, B, rng):
    """One batch ``(gk, types, valid)`` for a named case."""
    gk = rng.integers(0, K, B)
    types = rng.choice([A.CURRENT, A.EXPIRED], B, p=[0.7, 0.3])
    valid = rng.random(B) < 0.9
    if case == "reset_mid":
        types[[B // 3, B // 2]] = A.RESET
        valid[[B // 3, B // 2]] = True
    elif case == "reset_last":
        types[B - 1] = A.RESET
        valid[B - 1] = True
    elif case == "reset_first_and_timer":
        types[0] = A.RESET
        valid[0] = True
        types[1::7] = A.TIMER
    elif case == "all_invalid":
        valid[:] = False
    elif case == "key_absent":
        gk = np.where(gk == K // 2, (K // 2 + 1) % K, gk)
        gk = np.where(gk == 0, K - 1, gk)       # first and a middle key absent
    elif case == "one_key":
        gk[:] = K - 1
    elif case == "one_row_a_key":
        gk = np.arange(B) % K
    else:
        assert case == "plain"
    return gk.astype(np.int32), types.astype(np.int8), valid


@functools.lru_cache(maxsize=None)
def _jitted(K):
    """(specs, new, old) with both write-backs jitted, as the engine's step
    is: one compile a shape, shared by every case."""
    specs = [_spec(kind, t, i) for i, (kind, t) in enumerate(_SPECS)]
    new = jax.jit(lambda st, cols: A.apply_aggregators(specs, st, cols, CTX, K))
    old = jax.jit(lambda st, cols: _old_apply_aggregators(specs, st, cols, CTX, K))
    return specs, new, old


_CASES = ["plain", "reset_mid", "reset_last", "reset_first_and_timer",
          "all_invalid", "key_absent", "one_key", "one_row_a_key"]
# num_keys smaller than, equal to and larger than the batch, on both sides
# of ``_SCATTER_ABOVE_KEYS_PER_ROW`` (the last two scatter the batch)
_SHAPES = [(8, 96), (64, 64), (256, 48), (520, 64), (4096, 16)]


@pytest.mark.parametrize("K,B", _SHAPES)
@pytest.mark.parametrize("case", _CASES)
def test_writeback_bit_equal_to_batch_wide_scatter(case, K, B):
    rng = np.random.default_rng(zlib.crc32(f"{case}/{K}/{B}".encode()))
    specs, new, old = _jitted(K)
    state = A.init_agg_state(specs, K)
    old_state = state
    # two batches: the second folds the state the first wrote
    for _ in range(2):
        gk, types, valid = _batch(case, K, B, rng)
        cols = {"__gk__": jnp.asarray(gk), TYPE_KEY: jnp.asarray(types),
                VALID_KEY: jnp.asarray(valid),
                "v": jnp.asarray(rng.integers(-50, 50, B)),
                "v?": jnp.asarray(rng.random(B) < 0.1)}
        state, out = new(state, cols)
        old_state, old_out = old(old_state, cols)
        for i, spec in enumerate(specs):
            got, want = np.asarray(state[f"a{i}"]), np.asarray(old_state[f"a{i}"])
            assert got.dtype == want.dtype and got.shape == want.shape
            np.testing.assert_array_equal(
                got.view(np.uint8), want.view(np.uint8),
                err_msg=f"state of {spec.kind}({spec.arg_type})")
            for key in (spec.out_key, spec.out_key + "?"):
                if key in old_out:
                    a, b = np.asarray(out[key]), np.asarray(old_out[key])
                    assert a.dtype == b.dtype
                    np.testing.assert_array_equal(
                        a.view(np.uint8), b.view(np.uint8),
                        err_msg=f"{key} of {spec.kind}({spec.arg_type})")


@pytest.mark.parametrize("K,B", _SHAPES)
@pytest.mark.parametrize("case", ["plain", "all_invalid", "key_absent",
                                  "one_key", "one_row_a_key"])
def test_per_key_counts_equal_bincount(case, K, B):
    rng = np.random.default_rng(zlib.crc32(f"counts/{case}/{K}/{B}".encode()))
    pk, _types, valid = _batch(case, K, B, rng)
    order, inv_order, occ, counts, start_pos = _per_key_layout(
        jnp.asarray(pk, jnp.int64), jnp.asarray(valid), K)
    counts = np.asarray(counts)
    assert counts.dtype == np.int64 and counts.shape == (K,)
    np.testing.assert_array_equal(
        counts, np.bincount(pk[valid], minlength=K))
    # the other four values keep their meaning: occ is the arrival rank
    # within the key, start_pos the key's first sorted position
    occ, order, start_pos = map(np.asarray, (occ, order, start_pos))
    seen = np.zeros(K, np.int64)
    for i in np.flatnonzero(valid):
        assert occ[i] == seen[pk[i]]
        seen[pk[i]] += 1
        assert pk[order[start_pos[i]]] == pk[i] and valid[order[start_pos[i]]]
        assert start_pos[i] == 0 or not (
            valid[order[start_pos[i] - 1]]
            and pk[order[start_pos[i] - 1]] == pk[i])
    np.testing.assert_array_equal(np.asarray(inv_order)[order], np.arange(B))
