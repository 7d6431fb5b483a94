"""Native tokenshard loader: build, round-trip, gather, deterministic
shuffle, and native/fallback agreement."""

import os

import numpy as np
import pytest

from nanodiloco_tpu.data import tokenshard
from nanodiloco_tpu.data.tokenshard import (
    TokenShard,
    _py_shuffled_indices,
    native_available,
    write_shard,
)


@pytest.fixture(scope="module")
def shard_file(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("ts") / "train.tshrd")
    rng = np.random.default_rng(0)
    data = rng.integers(0, 32000, size=(100, 64), dtype=np.int32)
    write_shard(path, data)
    return path, data


def test_native_builds():
    """g++ is in the image; the native path must actually build here."""
    assert native_available()


def test_roundtrip_and_gather(shard_file):
    path, data = shard_file
    ts = TokenShard(path)
    assert (ts.n_seqs, ts.seq_len) == data.shape
    idx = np.asarray([0, 99, 42, 42, 7], dtype=np.uint64)
    np.testing.assert_array_equal(ts.batch(idx), data[idx.astype(int)])
    # full sweep, multithreaded
    all_idx = np.arange(100, dtype=np.uint64)
    np.testing.assert_array_equal(ts.batch(all_idx, n_threads=4), data)
    ts.close()


def test_gather_out_of_range(shard_file):
    path, _ = shard_file
    ts = TokenShard(path)
    with pytest.raises(IndexError):
        ts.batch(np.asarray([100], dtype=np.uint64))
    ts.close()


def test_shuffle_deterministic_and_distinct(shard_file):
    path, _ = shard_file
    ts = TokenShard(path)
    a = ts.shuffled_indices(seed=7, epoch=0, worker=0)
    b = ts.shuffled_indices(seed=7, epoch=0, worker=0)
    np.testing.assert_array_equal(a, b)
    assert sorted(a.tolist()) == list(range(100))  # a permutation
    c = ts.shuffled_indices(seed=7, epoch=1, worker=0)
    d = ts.shuffled_indices(seed=7, epoch=0, worker=1)
    assert not np.array_equal(a, c)
    assert not np.array_equal(a, d)
    ts.close()


def test_python_shuffle_matches_native(shard_file):
    """The numpy fallback must be bit-identical to the C++ Fisher-Yates,
    so mixed native/fallback hosts agree on batch order."""
    if not native_available():
        pytest.skip("no native lib to compare against")
    path, _ = shard_file
    ts = TokenShard(path)
    native = ts.shuffled_indices(seed=123, epoch=5, worker=3)
    py = _py_shuffled_indices(100, seed=123, epoch=5, worker=3)
    np.testing.assert_array_equal(native, py)
    ts.close()


def test_fallback_reader_matches_native(shard_file, monkeypatch):
    path, data = shard_file
    monkeypatch.setattr(tokenshard, "_lib", None)
    monkeypatch.setattr(tokenshard, "_lib_failed", True)
    ts = TokenShard(path)  # numpy memmap path
    idx = np.asarray([3, 1, 4], dtype=np.uint64)
    np.testing.assert_array_equal(ts.batch(idx), data[[3, 1, 4]])
    with pytest.raises(IndexError):
        ts.batch(np.asarray([1000], dtype=np.uint64))


def test_native_library_is_keyed_by_source_and_flags(monkeypatch):
    """The library that gets loaded is the one built from the committed
    source with today's flags: a file from other source, other flags or
    another machine (the old fixed ``libtokenshard.so``) has another
    name and is never picked up."""
    here = tokenshard._lib_path()
    assert os.path.dirname(here) == tokenshard._CSRC
    assert os.path.basename(here).startswith("libtokenshard-")
    assert os.path.basename(here) != "libtokenshard.so"
    assert "-march=native" not in tokenshard._FLAGS  # the tree is copied between machines
    monkeypatch.setattr(tokenshard, "_FLAGS", tokenshard._FLAGS + ("-DOTHER",))
    assert tokenshard._lib_path() != here


def test_failed_native_build_says_so_and_numpy_reader_serves(
    shard_file, monkeypatch, capfd
):
    """A build that fails is not silent: stderr names the reader in use
    and why, once, and the numpy reader answers the same reads."""
    path, data = shard_file
    monkeypatch.setattr(tokenshard, "_lib", None)
    monkeypatch.setattr(tokenshard, "_lib_failed", False)
    monkeypatch.setattr(tokenshard, "_FLAGS", ("--no-such-compiler-flag",))
    assert not native_available()
    assert not native_available()  # the second ask neither rebuilds nor repeats
    err = capfd.readouterr().err
    assert err.count("numpy reader in use") == 1
    assert "no-such-compiler-flag" in err
    ts = TokenShard(path)
    np.testing.assert_array_equal(
        ts.batch(np.asarray([5, 0], dtype=np.uint64)), data[[5, 0]]
    )


def test_bad_magic(tmp_path):
    p = tmp_path / "junk.tshrd"
    p.write_bytes(b"NOTASHARD" + b"\x00" * 64)
    with pytest.raises(OSError):
        TokenShard(str(p))


@pytest.mark.parametrize("flags", ["address,undefined", "thread"])
def test_native_layer_under_sanitizers(tmp_path, flags):
    """Build csrc under ASAN+UBSAN / TSAN and run the standalone harness
    (csrc/sanitize_test.cpp): every entry point incl. the multithreaded
    gather, clean under the sanitizers — the race-detection/sanitizer
    aux subsystem (SURVEY §5; the reference has no native code to
    sanitize)."""
    import os
    import shutil
    import subprocess

    if shutil.which("g++") is None:
        pytest.skip("no g++ in this environment")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    exe = str(tmp_path / f"ts_{flags.split(',')[0]}")
    build = subprocess.run(
        ["g++", "-std=c++17", "-g", f"-fsanitize={flags}",
         os.path.join(root, "csrc", "tokenshard.cpp"),
         os.path.join(root, "csrc", "sanitize_test.cpp"),
         "-o", exe, "-lpthread"],
        capture_output=True, text=True, timeout=240,
    )
    if build.returncode != 0:
        # g++ exists but the sanitizer runtime may not: match the LINKER's
        # missing-library text specifically — matching loosely (e.g. any
        # "sanitize") would also swallow real compile errors, whose
        # diagnostics name sanitize_test.cpp itself
        runtime_missing = any(
            pat in build.stderr
            for pat in ("cannot find -lasan", "cannot find -ltsan",
                        "cannot find -lubsan", "libasan", "libtsan", "libubsan")
        )
        if runtime_missing:
            pytest.skip(f"sanitizer runtime unavailable: {build.stderr[-200:]}")
        pytest.fail(f"sanitizer build failed:\n{build.stderr[-1500:]}")
    proc = subprocess.run(
        [exe, str(tmp_path)], capture_output=True, text=True, timeout=240
    )
    assert proc.returncode == 0, proc.stderr[-1500:]
    assert "sanitize_test OK" in proc.stdout


def test_shard_writer_bit_identical_to_one_pass(tmp_path):
    """The streaming materialization path (ShardWriter + chunked
    pack_corpus_to_shard at several forced-small flush sizes) must
    produce a byte-identical file to write_shard(pack_corpus(...)) —
    the past-RAM data path's correctness contract (VERDICT r3 #4)."""
    from nanodiloco_tpu.data import get_tokenizer, pack_corpus, pack_corpus_to_shard, synthetic_corpus
    from nanodiloco_tpu.data.tokenshard import ShardWriter

    texts = synthetic_corpus(n_docs=60, seed=3)
    tok = get_tokenizer(None)
    seq = 128
    one_pass = str(tmp_path / "one.tshrd")
    write_shard(one_pass, pack_corpus(texts, tok, seq))
    expect = open(one_pass, "rb").read()

    for flush_rows in (1, 3, 1024):
        p = str(tmp_path / f"stream{flush_rows}.tshrd")
        with ShardWriter(p, seq) as w:
            n = pack_corpus_to_shard(iter(texts), tok, seq, w, flush_rows=flush_rows)
        assert open(p, "rb").read() == expect, f"flush_rows={flush_rows}"
        ts = TokenShard(p)
        assert ts.n_seqs == n and ts.seq_len == seq
        ts.close()


def test_shard_writer_too_small_raises(tmp_path):
    from nanodiloco_tpu.data import get_tokenizer, pack_corpus_to_shard
    from nanodiloco_tpu.data.tokenshard import ShardWriter

    with ShardWriter(str(tmp_path / "t.tshrd"), 4096) as w:
        with pytest.raises(ValueError, match="corpus too small"):
            pack_corpus_to_shard(iter(["hi"]), get_tokenizer(None), 4096, w)


def test_shard_writer_rejects_bad_rows(tmp_path):
    from nanodiloco_tpu.data.tokenshard import ShardWriter

    with ShardWriter(str(tmp_path / "t.tshrd"), 8) as w:
        with pytest.raises(ValueError):
            w.append(np.zeros((2, 9), np.int32))


def test_shard_writer_atomic_on_failure(tmp_path):
    """A failed streaming run must not clobber a previously good shard:
    ShardWriter stages to .tmp and only installs on clean close."""
    from nanodiloco_tpu.data.tokenshard import ShardWriter

    p = str(tmp_path / "t.tshrd")
    good = np.arange(16, dtype=np.int32).reshape(2, 8)
    write_shard(p, good)
    before = open(p, "rb").read()
    with pytest.raises(RuntimeError):
        with ShardWriter(p, 8) as w:
            w.append(good)
            raise RuntimeError("boom")
    assert open(p, "rb").read() == before
    assert not os.path.exists(p + ".tmp")
