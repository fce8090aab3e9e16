"""The benchmark's copies (Zipf generator, numpy references) agree with
the program's own at small sizes, so the yardstick starts where the
program's tests stand."""
import numpy as np
import pytest

import reference
import zipfgen


@pytest.mark.parametrize("alpha", [0.0, 0.8, 1.5, 2.0])
def test_zipf_keys_match_program(alpha):
    from repro.data.zipf import zipf_keys
    for seed in (0, 7, 2**33 + 1):
        np.testing.assert_array_equal(
            zipfgen.zipf_keys(5000, 1 << 12, alpha, seed=seed),
            zipf_keys(5000, 1 << 12, alpha, seed=seed))


def test_murmur_matches_program():
    from repro.apps.hashes import murmur3_fmix32_np
    x = np.random.default_rng(0).integers(0, 2**32, 10000, dtype=np.uint64)
    np.testing.assert_array_equal(reference.murmur3_fmix32(x),
                                  murmur3_fmix32_np(x))


@pytest.mark.parametrize("bins,domain,m", [(512, 1 << 12, 4), (1000, 1 << 14, 16),
                                           (262144, 1 << 22, 16)])
def test_histo_matches_oracle(bins, domain, m):
    from repro.apps import histo
    keys = zipfgen.zipf_keys(20000, domain, 1.5, seed=3)
    ref = reference.RunningHisto(bins, domain, m)
    ref.add(keys[:7000])
    ref.add(keys[7000:])
    np.testing.assert_array_equal(ref.snapshot(),
                                  histo.oracle(keys, bins, domain, m))


@pytest.mark.parametrize("p,m", [(6, 4), (10, 16), (14, 16)])
def test_hll_matches_oracle(p, m):
    from repro.apps import hll
    keys = zipfgen.zipf_keys(20000, 1 << 22, 0.8, seed=4)
    ref = reference.RunningHLL(p, m)
    ref.add(keys[:123])
    ref.add(keys[123:])
    np.testing.assert_array_equal(ref.snapshot(), hll.oracle(keys, p, m))


def test_int16_control_wraps():
    ref = reference.RunningHisto(16, 16, 4)
    ref.add(np.zeros(40000, np.int64))
    assert ref.snapshot()[0, 0] == 40000
    assert ref.snapshot().astype(np.int16)[0, 0] != 40000
