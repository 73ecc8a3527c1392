"""The benchmark's own GF(256) reference agrees with the program's
oracle and with the program's parity kernel (interpreted here), and its
byte checks see every differing byte."""
import numpy as np
import pytest

from bench import kernels, reference


@pytest.mark.parametrize("k,p", [(4, 2), (8, 3), (2, 1)])
def test_encode_matches_program_oracle_and_kernel(k, p):
    from repro.kernels.rs_parity import ops, ref
    rng = np.random.default_rng(k * 10 + p)
    cells = rng.integers(0, 256, (k, 4096), dtype=np.uint8)
    want = reference.rs_encode(cells, p)
    assert np.array_equal(reference.cauchy_matrix(k, p),
                          ref.cauchy_matrix(k, p))
    assert np.array_equal(want, ref.rs_encode_np(cells, p))
    assert np.array_equal(want, np.asarray(ops.ec_encode(cells, p)))


def test_delta_matches_program_and_reencode():
    from repro.kernels.rs_parity import ops, ref
    k, p = 4, 2
    rng = np.random.default_rng(5)
    old = rng.integers(0, 256, (k, 2048), dtype=np.uint8)
    new = old.copy()
    new[2, 100:612] = rng.integers(0, 256, 512, dtype=np.uint8)
    deltas = (old[2:3] ^ new[2:3])
    d = reference.rs_parity_delta(k, p, [2], deltas)
    assert np.array_equal(d, ref.rs_parity_delta_np(k, p, [2], deltas))
    assert np.array_equal(d, np.asarray(ops.ec_parity_delta(k, p, [2],
                                                            deltas)))
    assert np.array_equal(reference.rs_encode(old, p) ^ d,
                          reference.rs_encode(new, p))


def test_stripe_cells_and_product_table():
    assert reference.GF_MUL[1, 77] == 77 and reference.GF_MUL[0, 5] == 0
    assert reference.GF_MUL[2, 0x80] == 0x1D         # x * x^7 mod 0x11D
    data = np.arange(1 << 12, dtype=np.uint32).astype(np.uint8)
    cells = reference.stripe_cells(data, 4, 2)
    assert cells.shape == (6, 1024)
    assert np.array_equal(cells[:4].reshape(-1), data)


def test_byte_checks():
    a = np.arange(16, dtype=np.float32)
    b = a.copy()
    assert reference.bytes_differing(a, b) == 0
    b.view(np.uint8)[5] ^= 1
    assert reference.bytes_differing(b, a) == 1
    assert reference.bytes_differing(a.reshape(4, 4), a) == a.nbytes
    nan = np.array([np.nan], np.float32)
    other = nan.copy()
    other.view(np.uint32)[0] ^= 1                    # another NaN payload
    assert reference.bytes_differing(other, nan) == 1


def test_kernel_bytes_are_the_algorithms():
    cells = np.zeros((4, 262144), np.uint8)
    assert kernels.rs_call_bytes("ec_encode", (cells, 2), {}) == 6 * 262144
    deltas = np.zeros((1, 4096), np.uint8)
    assert kernels.rs_call_bytes("ec_parity_delta", (4, 2, [1], deltas),
                                 {}) == 3 * 4096
    with pytest.raises(ValueError):
        kernels.rs_call_bytes("gf_matmul", (), {})


def test_seeded_data_is_repeatable_and_bf16_weights_are_normal():
    from bench import data
    a = data.random_bytes(np.random.default_rng(2 ** 31 + 9), 1001)
    b = data.random_bytes(np.random.default_rng(2 ** 31 + 9), 1001)
    assert a.size == 1001 and np.array_equal(a, b)
    w = data.bf16_weight_bytes(np.random.default_rng(3), 1 << 16)
    bits = w.view(np.uint16)
    exp = (bits >> 7) & 0xFF
    assert exp.min() == 112 and exp.max() == 127     # normal, finite
    assert len(np.unique(bits & 0x807F)) == 256      # sign, mantissa free
