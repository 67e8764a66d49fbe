"""Native C++ runtime (native/) vs pure-Python fallbacks — semantics must be
identical bit-for-bit, and the integrated paths (keys, csv connector,
persistence framing) must work with either."""

import logging
import os
import pickle
import re
import shutil
import struct
import subprocess
import sys

import numpy as np
import pytest

from pathway_tpu import native
from pathway_tpu.native import fallback
from pathway_tpu.internals import keys as K

from .utils import REPO_ROOT


CSV_CASES = [
    b"",
    b"a,b,c\n1,2,3\n",
    b"a,b\r\n1,2\r\n",
    b"no_newline_at_eof",
    b'q,"quoted,comma",3\n',
    b'"esc""aped",2\n',
    b'"multi\nline",2\n',
    b"a,b,\n",           # trailing empty cell
    b"a,b,",             # trailing delimiter at EOF
    b"\n\n",             # empty lines
    b"x\n\ny\n",
    b'",",","\n',
]


def test_native_library_builds(needs_native):
    assert native.available()


# -- which file is the library of these sources (ISSUE 30) -------------------


@pytest.fixture
def own_native_dir(tmp_path, monkeypatch):
    """The module pointed at a copy of ``native/`` with no build directory,
    and made to forget the library it has loaded (both undone afterwards)."""
    root = tmp_path / "native"
    for sub in ("src", "include"):
        shutil.copytree(native._NATIVE_DIR / sub, root / sub)
    monkeypatch.setattr(native, "_NATIVE_DIR", root)
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_lib_held", None)
    monkeypatch.setattr(native, "_tried", False)
    return root


def test_six_first_users_at_once_all_load_one_whole_library(needs_native, own_native_dir):
    """Tier-1's six workers, a serve fleet's replicas: every process that
    finds no library builds it and publishes it by rename, so each loads a
    whole file and one file is left.  A barrier on stdin makes them start
    together whatever their imports took."""
    child = (
        "import pathlib, sys, pathway_tpu.native as n\n"
        "n._NATIVE_DIR = pathlib.Path(sys.argv[1])\n"
        "print('ready', flush=True)\n"
        "sys.stdin.readline()\n"
        "print(n.available())\n"
    )
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", child, str(own_native_dir)], cwd=REPO_ROOT,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        for _ in range(6)
    ]
    try:
        for p in procs:
            assert p.stdout.readline().strip() == "ready", p.stderr.read()
        for p in procs:
            p.stdin.write("\n")
            p.stdin.flush()
        said = [p.communicate(timeout=300) for p in procs]
    finally:
        for p in procs:
            p.kill()
    assert [out.strip() for out, _ in said] == ["True"] * 6, said
    left = sorted(p.name for p in (own_native_dir / "build").iterdir())
    assert left == [native._library_path(native._recipe()).name], left


def test_the_name_is_a_function_of_recipe_and_sources(own_native_dir, monkeypatch):
    def name():
        return native._library_path(native._recipe()).name

    first = name()
    assert first == name() and first.endswith(".so")
    seen = {first}
    for rel in ("src/csv.cc", "include/pathway_native.h"):
        path = own_native_dir / rel
        was = path.read_bytes()
        path.write_bytes(was + b"\n")
        seen.add(name())
        path.write_bytes(was)
        assert name() == first
    (own_native_dir / "src" / "extra.cc").write_text("int pn_extra;\n")
    seen.add(name())
    (own_native_dir / "src" / "extra.cc").unlink()
    monkeypatch.setenv("CXX", "some-other-c++")
    seen.add(name())
    assert len(seen) == 5, seen


@pytest.mark.parametrize("compiler", ["fails", "missing"])
def test_a_failed_compile_leaves_no_file_and_says_so_once(
    needs_native, own_native_dir, monkeypatch, caplog, compiler
):
    cxx = own_native_dir / "cxx"
    if compiler == "fails":
        # writes half an output, as a linker killed mid-way would
        cxx.write_text(
            '#!/bin/sh\nwhile [ "$1" != -o ]; do shift; done\n'
            'echo half > "$2"\necho "boom: no such flag" >&2\nexit 1\n'
        )
        cxx.chmod(0o755)
    monkeypatch.setenv("CXX", str(cxx))
    with caplog.at_level(logging.WARNING, logger=native.__name__):
        assert native.lib() is None and native.lib() is None
        assert not native.available()
    assert len(caplog.records) == 1, caplog.text
    assert ("boom: no such flag" if compiler == "fails" else str(cxx)) in caplog.text
    assert list((own_native_dir / "build").iterdir()) == []
    # what a caller sees is the Python body's answer
    assert native.crc32(b"hello") == 0x3610A686
    assert native.encode_batch(
        b"a", np.array([0, 1]), 64, 8, 16, np.full(17, 16), 16, 1, 2, 0, 1
    ) is None


def test_the_library_has_every_name_the_header_declares(needs_native):
    header = (native._NATIVE_DIR / "include" / "pathway_native.h").read_text()
    declared = sorted(set(re.findall(r"\b(pn_\w+)\(", header)))
    assert len(declared) >= 13 and "pn_pack_rows" in declared
    dll = native.lib()
    missing = [name for name in declared if not hasattr(dll, name)]
    assert not missing, missing


@pytest.mark.parametrize("data", CSV_CASES)
def test_csv_scan_native_matches_fallback(data):
    got = native.csv_scan(data)
    want = fallback.csv_scan(data)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_csv_rows_against_csv_module():
    import csv
    import io

    data = b'a,b,c\n1,"x,""y""",3.5\n"multi\nline",2,3\n'
    want = list(csv.reader(io.StringIO(data.decode())))
    got = native.csv_rows(data)
    assert got == want


def test_csv_rows_post_quote_tail_matches_csv_module():
    """Text between a closing quote and the delimiter is kept verbatim,
    exactly like the python csv module."""
    import csv
    import io

    data = b'"Smith" Jr.,x\n"a" "b""c",2\n"q"tail"more",w\n"x" ,y\n'
    want = list(csv.reader(io.StringIO(data.decode())))
    got = native.csv_rows(data)
    assert got == want


def test_csv_unescape():
    assert native.csv_unescape(b'a""b""') == b'a"b"'
    assert native.csv_unescape(b"plain") == b"plain"
    # lone closing quote: drop it, tail verbatim
    assert native.csv_unescape(b'Smith" Jr.') == b"Smith Jr."
    assert native.csv_unescape(b'q"tail"more"') == b'qtail"more"'


def test_parse_int64_matches_fallback():
    cells = [b"123", b"-45", b"  7 ", b"9x", b"", b"+12", b"99999999999999999999"]
    data = b"".join(cells)
    off = np.cumsum([0] + [len(c) for c in cells[:-1]]).astype(np.int64)
    ln = np.array([len(c) for c in cells], dtype=np.int64)
    nv, nok = native.parse_int64(data, off, ln)
    fv, fok = fallback.parse_int64(data, off, ln)
    np.testing.assert_array_equal(nok, fok)
    np.testing.assert_array_equal(nv[nok == 1], fv[fok == 1])
    assert list(nok) == [1, 1, 1, 0, 0, 1, 0]


def test_parse_float64_matches_fallback():
    cells = [b"1.5", b"-2e3", b"nan", b"inf", b"abc", b"", b" 7 "]
    data = b"".join(cells)
    off = np.cumsum([0] + [len(c) for c in cells[:-1]]).astype(np.int64)
    ln = np.array([len(c) for c in cells], dtype=np.int64)
    nv, nok = native.parse_float64(data, off, ln)
    fv, fok = fallback.parse_float64(data, off, ln)
    np.testing.assert_array_equal(nok, fok)
    np.testing.assert_allclose(
        nv[(nok == 1) & ~np.isnan(nv)], fv[(fok == 1) & ~np.isnan(fv)]
    )


def test_serialize_rows_matches_python_serializer():
    cols = [
        [1, 2, None],
        ["a", None, "ccc"],
        [1.5, float("nan"), -0.0],
        [True, False, None],
        [K.Pointer(11), K.Pointer(12), K.Pointer(13)],
        [b"x", b"", b"yz"],
    ]
    n = len(cols[0])
    specs = [K._native_col_spec(c, n) for c in cols]
    assert all(s is not None for s in specs)
    buf, offs = native.serialize_rows(
        n, [s[0] for s in specs], [s[1] for s in specs], [s[2] for s in specs]
    )
    fbuf, foffs = fallback.serialize_rows(
        n, [s[0] for s in specs], [s[1] for s in specs], [s[2] for s in specs]
    )
    assert buf == fbuf
    np.testing.assert_array_equal(offs, foffs)
    # byte-identical to the canonical per-value serializer
    for i in range(n):
        want = bytearray()
        for c in cols:
            K._serialize_value(c[i], want)
        assert buf[offs[i] : offs[i + 1]] == bytes(want)


def test_ref_scalars_batch_matches_ref_scalar():
    cols = [
        np.arange(50, dtype=np.int64),
        [f"s{i}" if i % 3 else None for i in range(50)],
        np.linspace(0, 1, 50),
    ]
    batch = K.ref_scalars_batch(cols)
    for i in range(50):
        assert batch[i] == K.ref_scalar(cols[0][i], cols[1][i], cols[2][i])


def test_crc32_is_zlib_compatible():
    import zlib

    for data in (b"", b"hello", bytes(range(256)) * 7):
        assert native.crc32(data) == zlib.crc32(data) & 0xFFFFFFFF


def test_frame_scan_roundtrip_and_corruption():
    from pathway_tpu.persistence.framing import frame, scan

    records = [b"one", b"", b"three" * 100, pickle.dumps({"k": 1})]
    blob = b"".join(frame(r) for r in records)
    payloads, intact = scan(blob)
    assert payloads == records and intact

    # truncated tail -> valid prefix only
    payloads, intact = scan(blob[:-3])
    assert payloads == records[:-1] and not intact

    # corrupt a payload byte in the middle of record 2
    bad = bytearray(blob)
    off = len(frame(records[0])) + len(frame(records[1])) + 8 + 2
    bad[off] ^= 0xFF
    payloads, intact = scan(bytes(bad))
    assert payloads == records[:2] and not intact

    # native and fallback agree
    for data in (blob, blob[:-3], bytes(bad)):
        n_offs, n_lens, n_cons = native.frame_scan(data)
        f_offs, f_lens, f_cons = fallback.frame_scan(data)
        np.testing.assert_array_equal(n_offs, f_offs)
        np.testing.assert_array_equal(n_lens, f_lens)
        assert n_cons == f_cons


def test_shard_rows_matches_fallback():
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 2**64, size=1000, dtype=np.uint64)
    for n_shards in (1, 2, 7, 16):
        nc, no = native.shard_rows(keys, n_shards, K.SHARD_MASK)
        fc, fo = fallback.shard_rows(keys, n_shards, K.SHARD_MASK)
        np.testing.assert_array_equal(nc, fc)
        np.testing.assert_array_equal(no, fo)
        # permutation is stable and groups by shard
        shards = (keys & np.uint64(K.SHARD_MASK)) % np.uint64(n_shards)
        grouped = shards[no]
        assert (np.diff(grouped) >= 0).all()
        assert nc.sum() == len(keys)


def test_persistence_chunks_survive_torn_write(tmp_path):
    """A chunk with a torn tail replays its intact prefix."""
    from pathway_tpu.persistence.backends import MemoryBackend
    from pathway_tpu.persistence.engine_state import SourcePersistence

    backend = MemoryBackend()
    sp = SourcePersistence(backend, "src1")
    events = [(1, i, (f"row{i}",)) for i in range(10)]
    for e in events:
        sp.record(e)
    sp.flush(frontier=100)

    # tear the chunk
    key = "sources/src1/chunk-00000000"
    blob = backend.get(key)
    backend.put(key, blob[: len(blob) - 5])

    sp2 = SourcePersistence(backend, "src1")
    replayed = sp2.replay_events()
    assert replayed == events[:-1]
