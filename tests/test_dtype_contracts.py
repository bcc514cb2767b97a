"""Runtime twin of the ``dtype-pack-contract`` static rule (ISSUE 7
satellite): assert the IMPORTED layout authorities agree with each
other, so a drift that somehow slips past the static fold still fails
tier-1.

Three authorities must stay in lockstep (docs/STATIC_ANALYSIS.md):

- ``FLIGHT_DTYPE`` (observability/flight.py) vs the recorder's
  whole-row ``struct.pack_into`` format (``"<%dq" % len(names)``);
- ``LANE_DTYPE`` (backends/dispatcher.py) vs the 32-byte C layout the
  native library and the resolution fast path's ``bytes.join`` ->
  ``np.frombuffer`` reassembly assume;
- the static checker's own model of both declarations (the AST fold
  in analysis/contracts.py) vs the live numpy objects — if the
  parser's arithmetic ever drifts from numpy's, this is the test
  that says so.
"""

import struct

import numpy as np

from ratelimit_tpu.analysis.contracts import parse_dtype_decls
from ratelimit_tpu.analysis.engine import build_context
from ratelimit_tpu.analysis.project import ModuleInfo, module_name_for
from ratelimit_tpu.backends.dispatcher import LANE_DTYPE, LanePack, Lane
from ratelimit_tpu.observability.flight import FLIGHT_DTYPE, FlightRecorder


# -- FLIGHT_DTYPE vs the recorder's pack format ------------------------------


def test_flight_dtype_is_all_int64_and_word_aligned():
    for name in FLIGHT_DTYPE.names:
        field_dtype, offset = FLIGHT_DTYPE.fields[name]
        assert field_dtype == np.int64, name
        assert offset % 8 == 0, name
    assert FLIGHT_DTYPE.itemsize == 8 * len(FLIGHT_DTYPE.names)


def test_flight_pack_format_matches_dtype():
    """The exact format string flight.py builds must cover the row
    byte-for-byte: same total size, one little-endian int64 per field
    at the field's offset."""
    fmt = "<%dq" % len(FLIGHT_DTYPE.names)
    assert struct.calcsize(fmt) == FLIGHT_DTYPE.itemsize
    # offsets: the i-th packed value lands at the i-th field's offset
    for i, name in enumerate(FLIGHT_DTYPE.names):
        assert FLIGHT_DTYPE.fields[name][1] == i * 8, name


def test_flight_packed_row_reads_back_field_for_field():
    """Stamp one record through the real writer and read the ring
    back through the STRUCTURED view: every field round-trips."""
    rec = FlightRecorder(size=4)
    rec.note(stem_hash=0xABCD, lane=3)
    rec.record(domain="d", code=2, hits_addend=7, latency_ms=12.0)
    [row] = rec.snapshot()
    assert row["seq"] == 1
    assert row["stem"] == 0xABCD
    assert row["lane"] == 3
    assert row["code"] == 2
    assert row["hits"] == 7


# -- LANE_DTYPE vs the 32-byte C layout --------------------------------------

#: The C-struct layout the native library and the fast path's
#: pre-serialized template bytes assume: i64 at 0, six u32s after.
_LANE_STRUCT = struct.Struct("<q6I")
_LANE_OFFSETS = {
    "expiry": 0,
    "hits": 8,
    "limits": 12,
    "len": 16,
    "shadow": 20,
    "divider": 24,
    "algo": 28,
}


def test_lane_dtype_layout_is_pinned():
    """PR 6 widened the lane record 24 -> 32 bytes; this pins every
    field's offset and the itemsize so the next widening must update
    the native consumers (and this test) together."""
    assert LANE_DTYPE.itemsize == _LANE_STRUCT.size == 32
    assert list(LANE_DTYPE.names) == list(_LANE_OFFSETS)
    for name, want in _LANE_OFFSETS.items():
        field_dtype, offset = LANE_DTYPE.fields[name]
        assert offset == want, name
        assert field_dtype.itemsize in (4, 8)
        assert offset % field_dtype.itemsize == 0, name  # natural alignment


def test_lane_struct_pack_frombuffer_round_trip():
    """A row packed with the C layout parses identically through the
    numpy dtype — the exact reinterpretation the collector does on
    concatenated template bytes."""
    raw = _LANE_STRUCT.pack(1234567890123, 5, 60, 11, 1, 3600, 2)
    [row] = np.frombuffer(raw, dtype=LANE_DTYPE)
    assert row["expiry"] == 1234567890123
    assert row["hits"] == 5
    assert row["limits"] == 60
    assert row["len"] == 11
    assert row["shadow"] == 1
    assert row["divider"] == 3600
    assert row["algo"] == 2


def test_lane_pack_from_lanes_matches_itemsize():
    pack = LanePack.from_lanes(
        [Lane(key="k" * 9, expiry=7, hits=1, limit=10, shadow=False)]
    )
    assert pack.meta.nbytes == LANE_DTYPE.itemsize
    assert pack.meta_u8.nbytes == LANE_DTYPE.itemsize


# -- the static checker's model vs the live objects --------------------------


def _static_decl(path, name):
    source = open(path, encoding="utf-8").read()
    ctx = build_context(path, source)
    mod = ModuleInfo(module_name_for(path), ctx)
    decls = {d.name: d for d in parse_dtype_decls(mod)}
    assert name in decls, f"{name} not statically parseable in {path}"
    return decls[name]


def test_static_model_matches_live_flight_dtype():
    decl = _static_decl(
        "ratelimit_tpu/observability/flight.py", "FLIGHT_DTYPE"
    )
    assert decl.itemsize == FLIGHT_DTYPE.itemsize
    assert [f[0] for f in decl.fields] == list(FLIGHT_DTYPE.names)
    for name in FLIGHT_DTYPE.names:
        assert decl.offsets[name] == FLIGHT_DTYPE.fields[name][1], name


def test_static_model_matches_live_lane_dtype():
    decl = _static_decl(
        "ratelimit_tpu/backends/dispatcher.py", "LANE_DTYPE"
    )
    assert decl.itemsize == LANE_DTYPE.itemsize
    assert [f[0] for f in decl.fields] == list(LANE_DTYPE.names)
    for name in LANE_DTYPE.names:
        assert decl.offsets[name] == LANE_DTYPE.fields[name][1], name


# -- the ctypes boundary: sk_assign_dedup_batch (ISSUE 16 satellite) ---------
#
# The fused dedup entry moves ten buffers across the FFI in one call
# and its group outputs feed the int32[5, padded] device pack that
# engine.py hands to step_serve_packed.  Pin all three layers against
# each other: the static C parser model, the live ctypes table, and
# the numpy dtypes of the buffers that cross.

import ctypes

from ratelimit_tpu.analysis.cparse import parse_sources
from ratelimit_tpu.analysis.native_abi import find_native_sources
from ratelimit_tpu.backends import native_slot_table as nst

#: The agreed C signature, (param name, rendered type), in order.
_DEDUP_C_SIG = [
    ("tp", "void*"),
    ("key_blob", "uint8_t*"),
    ("key_lens", "int64_t*"),
    ("n", "int64_t"),
    ("now", "int64_t"),
    ("expiries", "int64_t*"),
    ("hits", "uint32_t*"),
    ("limits", "uint32_t*"),
    ("out_group", "int32_t*"),
    ("out_uniq", "int32_t*"),
    ("out_totals", "uint64_t*"),
    ("out_prefix", "uint64_t*"),
    ("out_freshg", "uint8_t*"),
    ("out_limitmax", "uint32_t*"),
    # CLOCK_MONOTONIC ns as the call's last act (ReturnStamp's cell).
    ("out_done_ns", "int64_t*"),
]

#: numpy dtype of each buffer the binding allocates/passes for the
#: pointer parameters above (native_slot_table.assign_dedup_packed).
_DEDUP_BUFFER_DTYPES = {
    "key_lens": np.int64,
    "expiries": np.int64,
    "hits": np.uint32,
    "limits": np.uint32,
    "out_group": np.int32,
    "out_uniq": np.int32,
    "out_totals": np.uint64,
    "out_prefix": np.uint64,
    "out_freshg": np.uint8,
    "out_limitmax": np.uint32,
    "out_done_ns": np.int64,  # ctypes.c_int64, same width
}


def _dedup_c_model():
    binding = "ratelimit_tpu/backends/native_slot_table.py"
    model = parse_sources(find_native_sources(binding))
    return model.functions["sk_assign_dedup_batch"]


def test_dedup_batch_static_c_signature_pinned():
    fn = _dedup_c_model()
    assert fn.ret.describe() == "int64_t"
    got = [(p.name, p.ctype.describe()) for p in fn.params]
    assert got == _DEDUP_C_SIG


def test_dedup_buffer_dtypes_match_c_pointee_widths():
    """Each numpy buffer that crosses the boundary has exactly the C
    pointee's element width — the runtime twin of the rule's
    call-site leg (an np.int32 buffer under a uint64_t* parameter is
    an out-of-bounds write the moment n > 0)."""
    fn = _dedup_c_model()
    by_name = {p.name: p.ctype for p in fn.params}
    for name, np_dtype in _DEDUP_BUFFER_DTYPES.items():
        c = by_name[name]
        assert c.is_pointer, name
        assert np.dtype(np_dtype).itemsize == c.width, name


def test_dedup_batch_live_argtypes_match_static():
    """The live ctypes table (pointer params as c_void_p raw
    addresses, scalars at the C width) agrees with the parsed
    signature — on the actually-loaded library when present."""
    if not nst.available():
        import pytest

        pytest.skip("native library unavailable in this environment")
    lib = ctypes.CDLL(nst.loaded_path())
    nst._signatures(lib)
    fn = _dedup_c_model()
    at = lib.sk_assign_dedup_batch.argtypes
    assert len(at) == len(fn.params) == 15
    for ct, param in zip(at, fn.params):
        if param.ctype.is_pointer:
            assert ct is ctypes.c_void_p, param.name
        else:
            assert ctypes.sizeof(ct) == param.ctype.width, param.name
    assert ctypes.sizeof(lib.sk_assign_dedup_batch.restype) == 8


def test_packed_transfer_u32_bit_views_are_lossless():
    """engine.py ships the dedup group outputs device-ward as an
    int32[5, padded] pack, reinterpreting the u32 rows (totals,
    limit_max, divider_max) via .view(np.int32).  That is only sound
    because the views are bit-exact both ways at width 4 — pinned
    here against the u32 saturation ceiling the native side clamps
    to (kU32Max)."""
    fn = _dedup_c_model()
    hits_c = {p.name: p.ctype for p in fn.params}["hits"]
    assert np.dtype(np.int32).itemsize == hits_c.width == 4
    totals = np.array([0, 1, 0x7FFFFFFF, 0x80000000, 0xFFFFFFFF], np.uint32)
    assert (totals.view(np.int32).view(np.uint32) == totals).all()
    # LANE_DTYPE's u32 counters are what those buffers are built from.
    assert LANE_DTYPE.fields["hits"][0] == np.dtype(np.uint32)
    assert LANE_DTYPE.fields["limits"][0] == np.dtype(np.uint32)


# -- LAUNCH_DTYPE vs the launch recorder's pack format (ISSUE 41) -------------


def test_launch_dtype_is_all_int64_and_its_pack_covers_every_field():
    """Four ledger fields joined the launch record; the recorder still
    stamps a whole row through ONE struct.pack_into of little-endian
    int64s, so the dtype must stay uniform and the format the recorder
    builds must cover it byte for byte, field i at offset 8 i."""
    from ratelimit_tpu.observability.launches import (
        LAUNCH_DTYPE,
        OUTCOME_OK,
        LaunchRecorder,
    )

    assert len(LAUNCH_DTYPE.names) == 22
    fmt = "<%dq" % len(LAUNCH_DTYPE.names)
    assert struct.calcsize(fmt) == LAUNCH_DTYPE.itemsize == 176
    for i, name in enumerate(LAUNCH_DTYPE.names):
        field_dtype, offset = LAUNCH_DTYPE.fields[name]
        assert field_dtype == np.int64, name
        assert offset == i * 8, name
    # One row through the real writer, every argument a distinct value:
    # each lands in the field of its name.
    lr = LaunchRecorder(2)
    args = list(range(101, 101 + len(LAUNCH_DTYPE.names) - 2))
    args[8] = OUTCOME_OK  # outcome (after seq, ts_ns: the recorder's own)
    lr.record(*args)
    [row] = lr.snapshot()
    assert [int(row[n]) for n in LAUNCH_DTYPE.names[2:]] == args
