"""Property tests for the GF(256) Reed-Solomon parity kernel: for every
geometry (k,p) <= (8,3), any loss pattern of up to p cells — data,
parity, or mixed — must decode bit-exactly from any k survivors, at
arbitrary cell sizes, and the Pallas dispatch must match the numpy
oracle. Skipped when hypothesis isn't installed (the kernel's fixed-case
coverage lives in test_kernels-style deterministic tests and the
erasure-path suites)."""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels.rs_parity import (ec_decode, ec_encode,  # noqa: E402
                                     ec_parity_delta)
from repro.kernels.rs_parity.ref import (cauchy_matrix, gf_inv,  # noqa: E402
                                         gf_mul, rs_decode_np, rs_encode_np,
                                         rs_parity_delta_np)


@st.composite
def _geometry(draw):
    k = draw(st.integers(1, 8))
    p = draw(st.integers(1, 3))
    n_lost = draw(st.integers(1, p))
    lost = draw(st.sets(st.integers(0, k + p - 1),
                        min_size=n_lost, max_size=n_lost))
    size = draw(st.integers(1, 257))
    seed = draw(st.integers(0, 2**31 - 1))
    return k, p, sorted(lost), size, seed


@settings(max_examples=60, deadline=None)
@given(_geometry())
def test_any_p_subset_recovers(geo):
    """MDS property end-to-end: erase ANY <= p of the k+p cells and the
    surviving k (arbitrary mix of data and parity) reconstruct every
    data cell bit-exactly."""
    k, p, lost, size, seed = geo
    cells = np.random.default_rng(seed).integers(
        0, 256, (k, size), dtype=np.uint8)
    parity = rs_encode_np(cells, p)
    stripe = np.concatenate([cells, parity], axis=0)
    present = [i for i in range(k + p) if i not in lost][:k]
    missing_data = [i for i in range(k) if i not in present]
    if not missing_data:
        return
    out = rs_decode_np(stripe[present], present, k, p, missing_data)
    np.testing.assert_array_equal(out, cells[missing_data])


@settings(max_examples=20, deadline=None)
@given(_geometry())
def test_kernel_dispatch_matches_numpy_oracle(geo):
    """ec_encode / ec_decode (the Pallas path the write fan-out and the
    degraded/rebuild paths call) agree with the pure-numpy oracle on the
    same survivors."""
    k, p, lost, size, seed = geo
    cells = np.random.default_rng(seed).integers(
        0, 256, (k, size), dtype=np.uint8)
    parity = np.asarray(ec_encode(cells, p))
    np.testing.assert_array_equal(parity, rs_encode_np(cells, p))
    stripe = np.concatenate([cells, parity], axis=0)
    present = [i for i in range(k + p) if i not in lost][:k]
    missing = [i for i in range(k) if i not in present]
    if not missing:
        return
    out = np.asarray(ec_decode(stripe[present], present, k, p, missing))
    np.testing.assert_array_equal(out, cells[missing])


@settings(max_examples=60, deadline=None)
@given(st.integers(1, 8), st.integers(1, 3))
def test_cauchy_generator_is_mds(k, p):
    """Every square submatrix of the systematic generator stays
    invertible — equivalently every p x p minor of the Cauchy block is
    nonsingular, which is what makes any-k-of-(k+p) decodable."""
    c = cauchy_matrix(k, p)
    # Cauchy matrices have an explicit determinant formula; nonzero as
    # long as the x_i and y_j are distinct, which the construction
    # guarantees. Spot-check via the linear-algebra route for 1x1 and
    # 2x2 minors (the sizes p <= 3 exercises).
    for j in range(p):
        for i in range(k):
            assert c[j][i] != 0
    if p >= 2:
        for j1 in range(p):
            for j2 in range(j1 + 1, p):
                for i1 in range(k):
                    for i2 in range(i1 + 1, k):
                        det = gf_mul(c[j1][i1], c[j2][i2]) ^ \
                            gf_mul(c[j1][i2], c[j2][i1])
                        assert det != 0


@settings(max_examples=100, deadline=None)
@given(st.integers(1, 255))
def test_gf_inverse(x):
    assert gf_mul(x, gf_inv(x)) == 1


@st.composite
def _delta_case(draw):
    """A stripe plus an arbitrary partial overwrite: any non-empty subset
    of the k data cells, each touched over its own sub-window."""
    k = draw(st.integers(1, 8))
    p = draw(st.integers(1, 3))
    size = draw(st.integers(1, 257))
    n_touch = draw(st.integers(1, k))
    touched = sorted(draw(st.sets(st.integers(0, k - 1),
                                  min_size=n_touch, max_size=n_touch)))
    windows = []
    for _ in touched:
        lo = draw(st.integers(0, size - 1))
        ln = draw(st.integers(1, size - lo))
        windows.append((lo, ln))
    seed = draw(st.integers(0, 2**31 - 1))
    return k, p, size, touched, windows, seed


@settings(max_examples=60, deadline=None)
@given(_delta_case())
def test_delta_parity_matches_full_reencode(case):
    """GF(256) linearity, the property the client's delta-RMW write path
    rides: for ANY sub-cell overwrite of ANY subset of data cells,
    P' = P xor ec_parity_delta(touched, old xor new) equals the parity of
    a full re-encode — so updating only the touched cells' deltas is
    bit-exact across every (k, p) <= (8, 3)."""
    k, p, size, touched, windows, seed = case
    rng = np.random.default_rng(seed)
    cells = rng.integers(0, 256, (k, size), dtype=np.uint8)
    parity = rs_encode_np(cells, p)
    new_cells = cells.copy()
    deltas = np.zeros((len(touched), size), np.uint8)
    for r, (i, (lo, ln)) in enumerate(zip(touched, windows)):
        fresh = rng.integers(0, 256, ln, dtype=np.uint8)
        deltas[r, lo:lo + ln] = new_cells[i, lo:lo + ln] ^ fresh
        new_cells[i, lo:lo + ln] = fresh
    pdelta = np.asarray(ec_parity_delta(k, p, touched, deltas))
    np.testing.assert_array_equal(pdelta,
                                  rs_parity_delta_np(k, p, touched, deltas))
    np.testing.assert_array_equal(parity ^ pdelta,
                                  rs_encode_np(new_cells, p))


def _cache_key_schedule():
    """Every call of the cross-key test: every touched subset of ec(4,2)
    through the delta leg, encode at ec(4,2) and ec(8,3), and ten
    decode (present, missing) sets, twice over, the second round in
    another order so each key is hit after others."""
    from itertools import combinations
    calls = [("delta", 4, 2, list(t)) for r in range(1, 5)
             for t in combinations(range(4), r)]
    calls += [("encode", 4, 2, None), ("encode", 8, 3, None)]
    # the same missing cells from other survivors, or from the same
    # survivors in another row order, need another matrix
    calls += [("decode", 4, 2, (present, missing)) for present, missing in [
        ([1, 2, 3, 4], [0]), ([1, 2, 3, 5], [0]), ([0, 2, 3, 5], [1]),
        ([2, 0, 3, 5], [1]), ([0, 1, 2, 5], [3]), ([2, 3, 4, 5], [0, 1]),
        ([0, 3, 4, 5], [1, 2]), ([3, 0, 5, 4], [1, 2]),
        ([1, 2, 4, 5], [0, 3]), ([1, 2, 4, 5], [3, 0])]]
    order = np.random.default_rng(14).permutation(len(calls))
    return calls + [calls[i] for i in order]


def _as_form(rows: np.ndarray, form: str):
    """The same u8 rows as a C-contiguous ndarray, a jax.Array, or a
    non-contiguous numpy view (every other byte of a wider buffer)."""
    if form == "jax":
        import jax.numpy as jnp
        return jnp.asarray(rows)
    if form == "view":
        wide = np.zeros((rows.shape[0], 2 * rows.shape[1]), np.uint8)
        wide[:, ::2] = rows
        view = wide[:, ::2]
        assert not view.flags.c_contiguous
        return view
    return rows


@pytest.mark.parametrize("form", ["numpy", "jax", "view"])
def test_coefficient_cache_keys_stay_bit_exact(form):
    """The device-resident coefficient cache hands each call the matrix
    of its own (leg, k, p, subset): interleaved delta, encode and decode
    calls at ec(4,2) and ec(8,3) all equal the ref.py oracle, whatever
    form the cell rows arrive in. A cache keyed too coarsely would give
    one touched subset or survivor set another's matrix."""
    rng = np.random.default_rng(1400)
    size = 131
    for leg, k, p, arg in _cache_key_schedule():
        if leg == "delta":
            rows = rng.integers(0, 256, (len(arg), size), dtype=np.uint8)
            got = ec_parity_delta(k, p, arg, _as_form(rows, form))
            want = rs_parity_delta_np(k, p, arg, rows)
        elif leg == "encode":
            rows = rng.integers(0, 256, (k, size), dtype=np.uint8)
            got = ec_encode(_as_form(rows, form), p)
            want = rs_encode_np(rows, p)
        else:
            present, missing = arg
            cells = rng.integers(0, 256, (k, size), dtype=np.uint8)
            stripe = np.concatenate([cells, rs_encode_np(cells, p)])
            got = ec_decode(_as_form(stripe[present], form), present, k, p,
                            missing)
            want = cells[missing]
        np.testing.assert_array_equal(np.asarray(got), want,
                                      err_msg=f"{leg} ec({k},{p}) {arg}")
