"""Native C++ kernel tests: build, bit-exact equivalence with the Python
fallbacks, and speed sanity."""

import numpy as np
import pytest

from deequ_tpu import native
from deequ_tpu.analyzers.scan import _classify_string
from deequ_tpu.ops.hll import XXHASH_SEED, xxhash64_bytes


@pytest.fixture(scope="module")
def built():
    if not native.available():
        pytest.skip("native toolchain unavailable")
    return True


SAMPLES = [
    "", "a", "hello world", "x" * 7, "y" * 8, "z" * 31, "w" * 32, "v" * 100,
    "unicode: äöü 中文 🎉", "123", "-42", "3.14", "true", "false", "  spaces  ",
    "O'Brien", "-", "+ 5", ".", "1.2.3",
]


def test_xxhash_matches_python(built):
    out = native.hash_strings(SAMPLES, XXHASH_SEED)
    expected = [xxhash64_bytes(s.encode("utf-8"), XXHASH_SEED) for s in SAMPLES]
    assert out.tolist() == expected


def test_xxhash_other_seed(built):
    a = native.hash_strings(["abc"], 1)
    b = native.hash_strings(["abc"], 2)
    assert a[0] != b[0]
    assert a[0] == xxhash64_bytes(b"abc", 1)


def test_classify_matches_python(built):
    out = native.classify_strings(SAMPLES)
    expected = [_classify_string(s) for s in SAMPLES]
    assert out.tolist() == expected


def test_utf8_lengths(built):
    out = native.utf8_lengths(SAMPLES)
    assert out.tolist() == [len(s) for s in SAMPLES]


def test_large_batch_consistency(built):
    rng = np.random.default_rng(0)
    values = [
        "".join(chr(rng.integers(32, 1000)) for _ in range(rng.integers(0, 50)))
        for _ in range(500)
    ]
    out = native.hash_strings(values, XXHASH_SEED)
    expected = [xxhash64_bytes(v.encode("utf-8"), XXHASH_SEED) for v in values]
    assert out.tolist() == expected
    assert native.utf8_lengths(values).tolist() == [len(v) for v in values]


def test_failed_build_is_reported_once_with_the_compilers_output(
    monkeypatch, tmp_path, capfd
):
    """A g++ that refuses kernels.cpp must not vanish: the compiler's own
    words reach stderr (once — the load is attempted once per process) and
    the Python path takes over."""
    import shutil

    if shutil.which("g++") is None:
        pytest.skip("no g++ to refuse anything")
    broken = tmp_path / "kernels.cpp"
    broken.write_text("this is not C++;\n")
    monkeypatch.setattr(native, "_SRC", str(broken))
    monkeypatch.setattr(native, "_SO", str(tmp_path / "_kernels.so"))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_attempted", False)
    assert native.available() is False
    assert native.hash_strings(["abc"], 1) is None  # callers fall back
    assert native.available() is False
    err = capfd.readouterr().err
    assert err.count("deequ_tpu.native: building") == 1
    assert "error" in err and "kernels.cpp" in err
