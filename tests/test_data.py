"""Native C++ data loader: build, determinism, sharding, resume."""

import numpy as np
import pytest

from burst_attn_tpu.data import DataLoader, read_token_file, write_token_file


@pytest.fixture(scope="module")
def token_file(tmp_path_factory):
    path = tmp_path_factory.mktemp("data") / "tokens.batd"
    rng = np.random.default_rng(0)
    write_token_file(path, rng.integers(0, 50000, size=100_000, dtype=np.int64))
    return path


def test_roundtrip_file(tmp_path):
    path = tmp_path / "t.batd"
    toks = np.arange(1000, dtype=np.int64) % 300
    write_token_file(path, toks)
    back = read_token_file(path)
    assert back.dtype == np.uint16
    np.testing.assert_array_equal(back, toks.astype(np.uint16))


def test_uint32_when_large_vocab(tmp_path):
    path = tmp_path / "t.batd"
    write_token_file(path, np.array([0, 70000, 123456]))
    assert read_token_file(path).dtype == np.uint32


def test_batches_shift_by_one(token_file):
    with DataLoader(token_file, batch=4, seq_len=128, shuffle=False) as dl:
        x, y = dl.next()
        assert x.shape == y.shape == (4, 128)
        np.testing.assert_array_equal(x[:, 1:], y[:, :-1])


def test_sequential_order_covers_file(token_file):
    toks = read_token_file(token_file)
    with DataLoader(token_file, batch=2, seq_len=64, shuffle=False) as dl:
        x, _ = dl.next()
        np.testing.assert_array_equal(x[0], toks[:64].astype(np.int32))
        np.testing.assert_array_equal(x[1], toks[65:129].astype(np.int32))


def test_deterministic_across_instances(token_file):
    def take(n):
        with DataLoader(token_file, batch=2, seq_len=128, seed=7) as dl:
            return [dl.next()[0] for _ in range(n)]

    a, b = take(5), take(5)
    for xa, xb in zip(a, b):
        np.testing.assert_array_equal(xa, xb)


def test_seek_resume_matches(token_file):
    with DataLoader(token_file, batch=2, seq_len=128, seed=3) as dl:
        batches = [dl.next()[0] for _ in range(6)]
    with DataLoader(token_file, batch=2, seq_len=128, seed=3) as dl:
        dl.seek(4)
        x4, _ = dl.next()
        x5, _ = dl.next()
    np.testing.assert_array_equal(x4, batches[4])
    np.testing.assert_array_equal(x5, batches[5])


def test_shards_disjoint_sequential(token_file):
    """Without shuffle, shard windows must be disjoint and interleaved."""
    starts = []
    for r in range(2):
        with DataLoader(token_file, batch=4, seq_len=64, shard_id=r,
                        num_shards=2, shuffle=False) as dl:
            x, _ = dl.next()
            starts.extend((r, int(x[i, 0])) for i in range(4))
    toks = read_token_file(token_file).astype(np.int32)
    # window w starts at w*(seq_len+1); rank r owns w % 2 == r
    for r, first in starts:
        w = [i for i in range(len(toks) // 65) if toks[i * 65] == first]
        assert any(i % 2 == r for i in w)


def test_shuffle_is_permutation(tmp_path):
    """One shuffled epoch visits every window exactly once (no replacement),
    so shard ownership stays disjoint under shuffle."""
    path = tmp_path / "perm.batd"
    wt, n_windows = 17, 23  # deliberately not powers of two
    write_token_file(path, np.arange(wt * n_windows) % 60000)
    firsts = []
    with DataLoader(path, batch=1, seq_len=wt - 1, seed=5, shuffle=True,
                    num_threads=1) as dl:
        for _ in range(n_windows):
            firsts.append(int(dl.next()[0][0, 0]))
    expected = {w * wt % 60000 for w in range(n_windows)}
    assert set(firsts) == expected
    assert len(set(firsts)) == n_windows
    assert firsts != sorted(firsts), "shuffle did nothing"


def test_close_right_after_next_comes_back(token_file):
    """`dl_close` sets its stop flag under the workers' mutex: without it a
    worker between its predicate and its wait missed the wake-up and the
    join never returned (under contention: tier-1 once hung here)."""
    import threading

    def churn():
        for _ in range(150):
            with DataLoader(token_file, batch=2, seq_len=32,
                            num_threads=1) as dl:
                dl.next()

    threads = [threading.Thread(target=churn, daemon=True) for _ in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=60)
    assert not any(th.is_alive() for th in threads)


def test_windows_per_epoch(token_file):
    with DataLoader(token_file, batch=1, seq_len=99, num_shards=4) as dl:
        assert dl.windows_per_epoch == (100_000 // 100) // 4
        assert dl.num_tokens == 100_000


def test_bad_file_rejected(tmp_path):
    p = tmp_path / "junk.bin"
    p.write_bytes(b"\x00" * 64)
    with pytest.raises(ValueError):
        DataLoader(p, batch=1, seq_len=8)


def test_too_small_file_rejected(tmp_path):
    p = tmp_path / "small.batd"
    write_token_file(p, np.arange(10))
    with pytest.raises(ValueError):
        DataLoader(p, batch=1, seq_len=100)


def test_prepare_cli_byte_level(tmp_path):
    from burst_attn_tpu.data.prepare import main

    a, b = tmp_path / "a.txt", tmp_path / "b.txt"
    a.write_text("hello world")
    b.write_text("abc")
    out = tmp_path / "corpus.batd"
    main([str(a), str(b), "--out", str(out), "--vocab-offset", "2",
          "--doc-sep", "1"])
    toks = read_token_file(out)
    assert len(toks) == 11 + 1 + 3
    assert toks[11] == 1  # separator between docs
    assert toks[0] == ord("h") + 2


@pytest.fixture
def native_copy(tmp_path, monkeypatch):
    """A private copy of native/ with no library built yet."""
    import shutil

    from burst_attn_tpu.data import loader

    shutil.copy(loader._native_dir() / "dataloader.cpp", tmp_path)
    monkeypatch.setattr(loader, "_native_dir", lambda: tmp_path)
    monkeypatch.setattr(loader, "_lib", None)
    return tmp_path


def test_library_rebuilds_on_source_hash_not_mtime(native_copy, monkeypatch):
    """The library is built on first use and again only when the hash
    recorded beside it is absent or is not the source's: a copy of the
    tree keeps no meaningful mtimes."""
    from burst_attn_tpu.data import loader

    builds = []
    real_build = loader._build_lib
    monkeypatch.setattr(loader, "_build_lib",
                        lambda src, out: (builds.append(1), real_build(src, out)))
    so = native_copy / "build" / "libdataloader.so"
    stamp = native_copy / "build" / "libdataloader.so.sha256"

    loader._load_lib()
    assert so.exists() and len(stamp.read_text().strip()) == 64
    monkeypatch.setattr(loader, "_lib", None)
    loader._load_lib()
    assert len(builds) == 1  # hash matches: no rebuild, whatever the mtimes
    stamp.write_text("stale\n")
    monkeypatch.setattr(loader, "_lib", None)
    loader._load_lib()
    assert len(builds) == 2
    stamp.unlink()
    monkeypatch.setattr(loader, "_lib", None)
    loader._load_lib()
    assert len(builds) == 3


def test_failed_build_raises_with_compiler_output(native_copy):
    from burst_attn_tpu.data import loader

    (native_copy / "dataloader.cpp").write_text("this is not c++\n")
    with pytest.raises(RuntimeError, match="error"):
        loader._load_lib()
    assert not (native_copy / "build" / "libdataloader.so").exists()
