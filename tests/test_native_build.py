"""The native extension is trusted only under the name its source hashes
to: ``*.so`` is git-ignored and a copied tree keeps no mtimes, so a stale
or foreign binary must never be loaded in place of a build of native.cpp.
"""

from __future__ import annotations

import os
import shutil

import pytest

from torchsnapshot_tpu import _native


@pytest.fixture
def fresh_loader(tmp_path, monkeypatch):
    """A copy of native.cpp in an empty directory, with the loader's
    process state reset."""
    if shutil.which("g++") is None:
        pytest.skip("no g++ on this host")
    monkeypatch.delenv(_native.DISABLE_NATIVE_ENV_VAR, raising=False)
    src = tmp_path / "native.cpp"
    shutil.copy(_native._SRC, src)
    monkeypatch.setattr(_native, "_SRC", str(src))
    monkeypatch.setattr(_native, "_build_info", {"so": None, "compiled_now": False})
    return src


def test_cache_key_follows_source_content_and_flags(fresh_loader, monkeypatch):
    first = _native._so_path()
    assert os.path.dirname(first) == str(fresh_loader.parent)
    with open(fresh_loader, "a") as f:
        f.write("\n// edited\n")
    assert _native._so_path() != first
    edited = _native._so_path()
    monkeypatch.setattr(_native, "_CXXFLAGS", _native._CXXFLAGS + ("-DX=1",))
    assert _native._so_path() != edited


def test_stale_binary_is_not_loaded_the_source_is_rebuilt(fresh_loader):
    # A binary left behind by an older native.cpp (newer mtime and all),
    # under the old fixed name and under another source's hash.
    for stale in ("_ts_native.so", "_ts_native.0123456789abcdef.so"):
        (fresh_loader.parent / stale).write_bytes(b"not an ELF file")
    lib = _native._try_load()
    assert lib is not None and lib.ts_crc32c(b"123456789", 9, 0) == 0xE3069283
    assert _native._build_info["compiled_now"] is True
    assert _native._build_info["so"] == _native._so_path()

    # The matching build is found again without compiling...
    _native._build_info["compiled_now"] = False
    assert _native._try_load() is not None
    assert _native._build_info["compiled_now"] is False
    # ...until the source changes.
    with open(fresh_loader, "a") as f:
        f.write("\n// edited\n")
    assert _native._try_load() is not None
    assert _native._build_info["compiled_now"] is True


def test_build_info_reports_the_loaded_binary():
    info = _native.build_info()
    assert info["available"] == _native.native_available()
    if info["available"]:
        assert info["built_from_source"] and info["so"] == _native._so_path()
