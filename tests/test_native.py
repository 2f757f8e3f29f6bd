"""Native (C++) ingest tests: build, parse parity, skip-gram semantics.

The toolchain (g++) is part of the supported environment, so these tests
require the native library to build; the ``available() is False`` fallback
path is covered separately by forcing the numpy branch.
"""

import numpy as np
import pytest

from fps_tpu import native


@pytest.fixture(scope="module")
def lib():
    assert native.available(), "g++ toolchain expected in this environment"
    return native


def test_parse_ratings_formats(lib, tmp_path):
    # ML-100K style: tab-separated ints with timestamp.
    p1 = tmp_path / "u.data"
    p1.write_text("1\t10\t3\t881250949\n2\t20\t5\t891717742\n3\t30\t1\t878887116\n")
    u, i, r = lib.parse_ratings(str(p1))
    np.testing.assert_array_equal(u, [1, 2, 3])
    np.testing.assert_array_equal(i, [10, 20, 30])
    np.testing.assert_allclose(r, [3.0, 5.0, 1.0])

    # ML-20M style: csv with header and float ratings.
    p2 = tmp_path / "ratings.csv"
    p2.write_text("userId,movieId,rating,timestamp\n1,2,3.5,1112486027\n7,8,4.0,1112484676\n")
    u, i, r = lib.parse_ratings(str(p2))
    np.testing.assert_array_equal(u, [1, 7])
    np.testing.assert_array_equal(i, [2, 8])
    np.testing.assert_allclose(r, [3.5, 4.0])

    assert lib.parse_ratings(str(tmp_path / "missing")) is None

    # Corrupted data lines must raise, not silently truncate.
    p3 = tmp_path / "bad.data"
    p3.write_text("1\t2\t3\n4\tgarbage\n5\t6\t1\n")
    with pytest.raises(ValueError, match="malformed"):
        lib.parse_ratings(str(p3))

    # A quoted-field csv must raise too — every line is non-digit-leading,
    # so nothing may be silently skipped as a "header".
    p4 = tmp_path / "quoted.csv"
    p4.write_text('"userId","movieId","rating"\n' + "".join(
        f'"{k}","{k+1}","3.5"\n' for k in range(20)))
    with pytest.raises(ValueError, match="malformed"):
        lib.parse_ratings(str(p4))

    # Non-digit garbage after data has started is malformed, not a header.
    p5 = tmp_path / "midfile.data"
    p5.write_text("1\t2\t3\noops line\n5\t6\t1\n")
    with pytest.raises(ValueError, match="malformed"):
        lib.parse_ratings(str(p5))

    # '#' comments are valid anywhere, including a long preamble.
    p6 = tmp_path / "commented.data"
    p6.write_text("".join(f"# preamble {k}\n" for k in range(10))
                  + "1\t2\t3\n# interlude\n4\t5\t2\n")
    u, i, r = lib.parse_ratings(str(p6))
    np.testing.assert_array_equal(u, [1, 4])
    np.testing.assert_allclose(r, [3.0, 2.0])


def test_parse_ratings_matches_loadtxt(lib, tmp_path):
    rng = np.random.default_rng(0)
    n = 5000
    rows = np.stack([
        rng.integers(1, 944, n),
        rng.integers(1, 1683, n),
        rng.integers(1, 6, n),
        rng.integers(0, 10**9, n),
    ], axis=1)
    p = tmp_path / "big.data"
    np.savetxt(p, rows, fmt="%d", delimiter="\t")
    u, i, r = lib.parse_ratings(str(p))
    raw = np.loadtxt(p, dtype=np.int64)
    np.testing.assert_array_equal(u, raw[:, 0])
    np.testing.assert_array_equal(i, raw[:, 1])
    np.testing.assert_allclose(r, raw[:, 2].astype(np.float32))


def test_load_movielens_uses_native(lib, tmp_path):
    from fps_tpu.utils.datasets import load_movielens

    p = tmp_path / "u.data"
    p.write_text("1\t1\t5\t0\n2\t2\t3\t0\n943\t1682\t1\t0\n")
    data, nu, ni = load_movielens(str(p))
    assert (nu, ni) == (943, 1682)
    np.testing.assert_array_equal(data["user"], [0, 1, 942])
    np.testing.assert_allclose(data["rating"], [5.0, 3.0, 1.0])


def test_skipgram_window1_exact(lib):
    """window=1, no subsampling: exactly the adjacent bidirectional pairs."""
    tokens = np.array([4, 7, 2, 9], np.int32)
    c, x = lib.skipgram_pairs(tokens, window=1, seed=0)
    want_c = [4, 7, 7, 2, 2, 9]
    want_x = [7, 4, 2, 7, 9, 2]
    np.testing.assert_array_equal(c, want_c)
    np.testing.assert_array_equal(x, want_x)


def test_skipgram_dynamic_window_validity_and_determinism(lib):
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, 50, 2000).astype(np.int32)
    c1, x1 = lib.skipgram_pairs(tokens, window=5, seed=42)
    c2, x2 = lib.skipgram_pairs(tokens, window=5, seed=42)
    np.testing.assert_array_equal(c1, c2)  # deterministic per seed
    c3, _ = lib.skipgram_pairs(tokens, window=5, seed=43)
    assert len(c3) != len(c1) or not np.array_equal(c1, c3)

    # Without subsampling the kept sequence is the input: each emitted pair
    # must occur somewhere in the stream within `window` positions.
    within = set()
    for t in range(len(tokens)):
        for d in range(1, 6):
            if t + d < len(tokens):
                within.add((int(tokens[t]), int(tokens[t + d])))
                within.add((int(tokens[t + d]), int(tokens[t])))
    assert all((int(a), int(b)) in within for a, b in zip(c1[:500], x1[:500]))
    # Expected count: sum over positions of 2*E[half] ≈ 2 * (w+1)/2 * n.
    expect = 2 * (5 + 1) / 2 * len(tokens)
    assert 0.8 * expect < len(c1) < 1.2 * expect


def test_skipgram_subsampling_drops_frequent(lib):
    tokens = np.zeros(5000, np.int32)  # all the same, maximally frequent
    tokens[::10] = 1
    keep_p = np.array([0.05, 1.0], np.float32)
    c, x = lib.skipgram_pairs(tokens, window=2, seed=7, keep_p=keep_p)
    kept0 = np.sum(c == 0) / max(len(c), 1)
    # token 0 is 90% of the stream but should be heavily subsampled away
    assert kept0 < 0.6
    c_all, _ = lib.skipgram_pairs(tokens, window=2, seed=7)
    assert len(c) < len(c_all) / 2


def test_skipgram_chunks_native_vs_numpy_stream(devices8):
    """Both generator paths feed identical-shape chunks and train."""
    from fps_tpu.models.word2vec import W2VConfig, skipgram_chunks

    rng = np.random.default_rng(2)
    tokens = rng.integers(0, 100, 30_000).astype(np.int32)
    uni = np.bincount(tokens, minlength=100).astype(np.float64)
    cfg = W2VConfig(vocab_size=100, dim=8, window=3, negatives=2)

    counts = {}
    for mode in (True, False):
        chunks = list(skipgram_chunks(
            tokens, uni, cfg, num_workers=4, local_batch=64,
            steps_per_chunk=2, seed=3, use_native=mode,
        ))
        for ch in chunks:
            assert ch["center"].shape == (2, 256)
        counts[mode] = sum(float(ch["weight"].sum()) for ch in chunks)
    # Same sampling scheme, different RNG draws: totals within 10%.
    assert abs(counts[True] - counts[False]) / counts[False] < 0.1


def test_parse_ratings_crlf_and_blank_lines(lib, tmp_path):
    """Windows line endings and blank lines (including mid-file and
    trailing) parse cleanly — a bare CR blank line must not count as
    malformed."""
    p = tmp_path / "crlf.csv"
    p.write_bytes(b"userId,movieId,rating\r\n1,2,3.5\r\n\r\n4,5,2.0\r\n\r\n")
    u, i, r = lib.parse_ratings(str(p))
    np.testing.assert_array_equal(u, [1, 4])
    np.testing.assert_array_equal(i, [2, 5])
    np.testing.assert_allclose(r, [3.5, 2.0])


def test_bound_symbols_are_exported_and_nothing_else_is(lib):
    """Every ``fps_*`` symbol the loader binds is exported by the library
    built from the source in the tree, and the library exports nothing the
    package does not bind: a function in ``fps_native.cc`` with no caller
    in ``fps_tpu`` has no reader."""
    import re
    import subprocess

    with open(lib.__file__) as f:
        bound = set(re.findall(r"\blib\.(fps_\w+)", f.read()))
    assert bound, "the loader binds no symbol"
    out = subprocess.run(["nm", "-D", "--defined-only", lib._LIB],
                         check=True, capture_output=True, text=True).stdout
    exported = set(re.findall(r"\b(fps_\w+)$", out, re.M))
    assert exported == bound, sorted(exported ^ bound)
    assert not [s for s in exported if s.startswith("fps_baseline_")]
