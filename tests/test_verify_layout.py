"""ggrs-verify pillar 1: the cross-language layout checker.

Three layers of pinning (ISSUE: the static-analysis plane):

* parser goldens — the C++/Python extractors read the exact constant
  shapes the native sources use (constexpr casts, enums with implicit
  increments, struct-format aliases);
* deliberate-skew fixtures — a 1-value mirror drift, a 1-byte header
  drift, and a jump-offset drift each FIRE (the tree is currently
  clean, so the fixtures are what prove the checker catches what it
  exists to catch);
* self-clean + runtime parity — the repo tree passes, and the static
  header table equals both the live ``np.dtype`` and the runtime
  ``ggrs_bank_hdr_stride()`` probe.
"""

from pathlib import Path

import numpy as np
import pytest

from ggrs_tpu.analysis import (
    LAYOUT_HEADER_FIELDS,
    check_layout,
    parse_cpp_constants,
    parse_py_constants,
    parse_py_struct_formats,
    static_bank_header,
)
from ggrs_tpu.analysis.layout import (
    LAYOUT_FD_FIELDS,
    LAYOUT_FD_STRIDE,
    LAYOUT_RECV_FIELDS,
    LAYOUT_RECV_STRIDE,
    LAYOUT_REQ_FIELDS,
    LAYOUT_REQ_STRIDE,
    LAYOUT_ROUTE_FIELDS,
    LAYOUT_ROUTE_STRIDE,
    LAYOUT_SEND_FIELDS,
    LAYOUT_SEND_STRIDE,
    LAYOUT_STAGE_FIELDS,
    LAYOUT_STAGE_STRIDE,
    LAYOUT_WIRE_FIELDS,
    LAYOUT_WIRE_STRIDE,
    MIRRORED_CONSTANTS,
    _check_field_table,
    _check_header,
    _check_mirrors,
)
from ggrs_tpu.net import _native

REPO = Path(__file__).resolve().parents[1]


# ----------------------------------------------------------------------
# parser goldens
# ----------------------------------------------------------------------


class TestCppParser:
    def test_constexpr_forms(self):
        src = """
        constexpr int kPlain = 42;
        constexpr int64_t kNeg = -70;
        constexpr size_t kShift = size_t{1} << 22;
        constexpr uint64_t kAllOnes = ~uint64_t{0};
        constexpr int64_t kNegShift = -(int64_t{1} << 62);
        constexpr uint8_t kHex = 0x80;
        static constexpr int kStatic = 7;
        """
        c = parse_cpp_constants(src)
        assert c["kPlain"] == 42
        assert c["kNeg"] == -70
        assert c["kShift"] == 1 << 22
        assert c["kAllOnes"] == (1 << 64) - 1
        assert c["kNegShift"] == -(1 << 62)
        assert c["kHex"] == 0x80
        assert c["kStatic"] == 7

    def test_enum_implicit_increment(self):
        src = """
        enum MsgTag : uint8_t {
          kTagA = 0,
          kTagB,      // implicit 1
          kTagC = 5,
          kTagD,      // implicit 6
        };
        enum class Verdict { kOk = 0, kErr = -3 };
        """
        c = parse_cpp_constants(src)
        assert (c["kTagA"], c["kTagB"], c["kTagC"], c["kTagD"]) == \
            (0, 1, 5, 6)
        assert c["kErr"] == -3

    def test_comments_do_not_confuse(self):
        src = """
        // constexpr int kCommented = 9;
        /* constexpr int kBlock = 10; */
        constexpr int kReal = 1;  // trailing = 2 garbage
        """
        c = parse_cpp_constants(src)
        assert c == {"kReal": 1}

    def test_non_integer_skipped(self):
        c = parse_cpp_constants(
            'constexpr char kName[] = "x";\n'
            "constexpr double kF = 1.5;\n"
            "constexpr int kOk = 3;\n"
        )
        assert c == {"kOk": 3}


class TestPySourceParser:
    def test_constants_and_folding(self):
        src = "A = 48\nB = 1 << 22\nC = -70\nD = A\n_E = 0x80\n"
        c = parse_py_constants(src)
        assert c == {"A": 48, "B": 1 << 22, "C": -70, "_E": 0x80}

    def test_struct_formats_direct_and_aliased(self):
        src = (
            "import struct\n"
            "from struct import unpack_from as uf\n"
            "pack = struct.pack\n"
            "H = struct.Struct('<2sBBII')\n"
            "def f(buf):\n"
            "    pack('<HI', 1, 2)\n"
            "    uf('<iqiqqBH', buf, 0)\n"
            "    struct.unpack('<qqq', buf)\n"
        )
        fmts = {(s.func, s.fmt) for s in parse_py_struct_formats(src)}
        assert ("Struct", "<2sBBII") in fmts
        assert ("pack", "<HI") in fmts
        assert ("unpack_from", "<iqiqqBH") in fmts
        assert ("unpack", "<qqq") in fmts


# ----------------------------------------------------------------------
# deliberate-skew fixtures: the checker must FIRE on drift
# ----------------------------------------------------------------------


def _mini_tree(tmp_path, native_py_text: str) -> Path:
    """A minimal fake repo holding just the files _check_header reads."""
    (tmp_path / "native").mkdir()
    (tmp_path / "ggrs_tpu/net").mkdir(parents=True)
    (tmp_path / "native/session_bank.cpp").write_text(
        "constexpr size_t kHdrStride = 48;\n"
    )
    (tmp_path / "ggrs_tpu/net/_native.py").write_text(native_py_text)
    return tmp_path


GOOD_FIELDS = (
    'BANK_HDR_FIELDS = (\n'
    '    ("flags", "<u4"), ("rec_len", "<u4"), ("err", "<i4"),\n'
    '    ("fa", "<i4"), ("landed", "<i8"), ("current", "<i8"),\n'
    '    ("confirmed", "<i8"), ("save_frame", "<i8"),\n'
    ')\n'
)


class TestDeliberateSkew:
    def test_clean_fixture_passes(self, tmp_path):
        root = _mini_tree(tmp_path, GOOD_FIELDS)
        assert _check_header(root) == []

    def test_one_byte_header_drift_fires(self, tmp_path):
        # err shrinks i4 -> i2: every later offset shifts, stride 46
        root = _mini_tree(
            tmp_path, GOOD_FIELDS.replace('("err", "<i4")',
                                          '("err", "<i2")')
        )
        findings = _check_header(root)
        assert findings, "1-byte field drift must fail lint"
        assert any("stride" in f.rule or "fields" in f.rule
                   for f in findings)

    def test_big_endian_field_fires(self, tmp_path):
        root = _mini_tree(
            tmp_path, GOOD_FIELDS.replace('("landed", "<i8")',
                                          '("landed", ">i8")')
        )
        assert any(
            f.rule == "layout/header-endian" for f in _check_header(root)
        )

    def test_native_stride_drift_fires(self, tmp_path):
        root = _mini_tree(tmp_path, GOOD_FIELDS)
        (root / "native/session_bank.cpp").write_text(
            "constexpr size_t kHdrStride = 56;\n"
        )
        assert any(
            f.rule == "layout/header-stride" for f in _check_header(root)
        )

    # ---- descriptor-plane structs (§21): same three layers of pinning --

    REQ_GOOD = (
        'BANK_REQ_FIELDS = (\n'
        '    ("pattern", "<u1"), ("rflags", "<u1"), ("n_adv", "<u2"),\n'
        '    ("adv_off", "<u4"), ("adv_stride", "<u4"),\n'
        '    ("ops_end", "<u4"), ("frame", "<i8"),\n'
        ')\n'
    )
    STAGE_GOOD = (
        'BANK_STAGE_FIELDS = (\n'
        '    ("slot", "<u4"), ("handle", "<i4"), ("frame", "<i8"),\n'
        '    ("off", "<u4"), ("len", "<u4"),\n'
        ')\n'
    )

    def _table_tree(self, tmp_path, text):
        (tmp_path / "ggrs_tpu/net").mkdir(parents=True)
        (tmp_path / "ggrs_tpu/net/_native.py").write_text(text)
        return tmp_path

    def test_clean_req_table_passes(self, tmp_path):
        root = self._table_tree(tmp_path, self.REQ_GOOD + self.STAGE_GOOD)
        assert _check_field_table(
            root, "BANK_REQ_FIELDS", LAYOUT_REQ_FIELDS, LAYOUT_REQ_STRIDE
        ) == []
        assert _check_field_table(
            root, "BANK_STAGE_FIELDS", LAYOUT_STAGE_FIELDS,
            LAYOUT_STAGE_STRIDE,
        ) == []

    def test_req_one_byte_drift_fires(self, tmp_path):
        # n_adv shrinks u2 -> u1: every later offset shifts, stride 23
        root = self._table_tree(
            tmp_path,
            self.REQ_GOOD.replace('("n_adv", "<u2")', '("n_adv", "<u1")'),
        )
        findings = _check_field_table(
            root, "BANK_REQ_FIELDS", LAYOUT_REQ_FIELDS, LAYOUT_REQ_STRIDE
        )
        assert findings, "1-byte descriptor field drift must fail lint"
        assert any("stride" in f.rule or "fields" in f.rule
                   for f in findings)

    WIRE_GOOD = (
        'BANK_WIRE_FIELDS = (\n'
        '    ("slot", "<u4"), ("ep", "<u2"), ("section", "<u1"),\n'
        '    ("off", "<u4"), ("len", "<u4"), ("crc", "<u4"),\n'
        ')\n'
    )

    @pytest.mark.parametrize("drift", [
        None,
        ('("section", "<u1")', '("section", "<u2")'),
        ('("off", "<u4"), ("len", "<u4")', '("len", "<u4"), ("off", "<u4")'),
    ], ids=["clean", "one-byte", "order"])
    def test_wire_table_row(self, tmp_path, drift):
        """The outbound table's row: clean passes; a widened field (stride
        20) or two swapped fields (same stride, offsets moved) fire."""
        text = self.WIRE_GOOD if drift is None else \
            self.WIRE_GOOD.replace(*drift)
        findings = _check_field_table(
            self._table_tree(tmp_path, text), "BANK_WIRE_FIELDS",
            LAYOUT_WIRE_FIELDS, LAYOUT_WIRE_STRIDE,
        )
        assert bool(findings) == (drift is not None)

    def test_stage_big_endian_fires(self, tmp_path):
        root = self._table_tree(
            tmp_path,
            self.STAGE_GOOD.replace('("frame", "<i8")',
                                    '("frame", ">i8")'),
        )
        assert any(
            f.rule == "layout/table-endian"
            for f in _check_field_table(
                root, "BANK_STAGE_FIELDS", LAYOUT_STAGE_FIELDS,
                LAYOUT_STAGE_STRIDE,
            )
        )

    RECV_GOOD = (
        'NET_RECV_FIELDS = (\n'
        '    ("slot", "<i4"), ("fd_idx", "<i4"), ("ip", "<u4"),\n'
        '    ("port", "<u2"), ("seg", "<u2"), ("off", "<u4"),\n'
        '    ("len", "<u4"),\n'
        ')\n'
    )
    ROUTE_GOOD = (
        'NET_ROUTE_FIELDS = (\n'
        '    ("ip", "<u4"), ("port", "<u2"), ("pad", "<u2"),\n'
        '    ("slot", "<i4"),\n'
        ')\n'
    )

    def test_clean_gen2_tables_pass(self, tmp_path):
        root = self._table_tree(tmp_path, self.RECV_GOOD + self.ROUTE_GOOD)
        assert _check_field_table(
            root, "NET_RECV_FIELDS", LAYOUT_RECV_FIELDS, LAYOUT_RECV_STRIDE
        ) == []
        assert _check_field_table(
            root, "NET_ROUTE_FIELDS", LAYOUT_ROUTE_FIELDS,
            LAYOUT_ROUTE_STRIDE,
        ) == []

    def test_recv_record_one_byte_drift_fires(self, tmp_path):
        # port widens u2 -> u4: off/len shift, stride 26 — the §23a
        # record table is a wire struct and must fail lint like one
        root = self._table_tree(
            tmp_path,
            self.RECV_GOOD.replace('("port", "<u2")', '("port", "<u4")'),
        )
        findings = _check_field_table(
            root, "NET_RECV_FIELDS", LAYOUT_RECV_FIELDS, LAYOUT_RECV_STRIDE
        )
        assert findings, "recv-record field drift must fail lint"

    def test_route_row_field_order_drift_fires(self, tmp_path):
        # slot moves ahead of ip: same stride, different offsets — the
        # native binary search would read garbage keys
        root = self._table_tree(
            tmp_path,
            'NET_ROUTE_FIELDS = (\n'
            '    ("slot", "<i4"), ("ip", "<u4"), ("port", "<u2"),\n'
            '    ("pad", "<u2"),\n'
            ')\n',
        )
        findings = _check_field_table(
            root, "NET_ROUTE_FIELDS", LAYOUT_ROUTE_FIELDS,
            LAYOUT_ROUTE_STRIDE,
        )
        assert findings, "route-row field order drift must fail lint"

    def test_recv_stride_mirror_drift_fires(self, tmp_path):
        (tmp_path / "a.cpp").write_text(
            "constexpr size_t kRecvStride = 28;\n"
        )
        (tmp_path / "b.py").write_text("NET_RECV_STRIDE = 24\n")
        findings = _check_mirrors(
            tmp_path, [("a.cpp", "kRecvStride", "b.py", "NET_RECV_STRIDE")]
        )
        assert [f.rule for f in findings] == ["layout/mirror-mismatch"]

    def test_send_stride_mirror_drift_fires(self, tmp_path):
        # the C++ kSendStride is pinned through the mirror table — a
        # native-side stride bump without the Python twin fires
        (tmp_path / "a.cpp").write_text("constexpr size_t kSendStride = 24;\n")
        (tmp_path / "b.py").write_text("NET_SEND_STRIDE = 20\n")
        findings = _check_mirrors(
            tmp_path, [("a.cpp", "kSendStride", "b.py", "NET_SEND_STRIDE")]
        )
        assert [f.rule for f in findings] == ["layout/mirror-mismatch"]

    def test_mirror_value_drift_fires(self, tmp_path):
        (tmp_path / "a.cpp").write_text("constexpr int kX = -70;\n")
        (tmp_path / "b.py").write_text("X = -71\n")
        findings = _check_mirrors(
            tmp_path, [("a.cpp", "kX", "b.py", "X")]
        )
        assert [f.rule for f in findings] == ["layout/mirror-mismatch"]

    def test_mirror_missing_side_fires(self, tmp_path):
        (tmp_path / "a.cpp").write_text("constexpr int kX = -70;\n")
        (tmp_path / "b.py").write_text("OTHER = 1\n")
        findings = _check_mirrors(
            tmp_path, [("a.cpp", "kX", "b.py", "X")]
        )
        assert [f.rule for f in findings] == ["layout/mirror-missing"]


# ----------------------------------------------------------------------
# the tree itself + runtime parity
# ----------------------------------------------------------------------


class TestTreeIsClean:
    def test_repo_layout_clean(self):
        findings = check_layout(REPO)
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_mirror_table_covers_all_bank_errors(self):
        """Every kBankErr*/kHdr* the native source declares is in the
        mirror table — a NEW native constant without a declared mirror
        fails here, which is how the table stays complete."""
        native = parse_cpp_constants(REPO / "native/session_bank.cpp")
        mirrored = {
            c for f, c, _, _ in MIRRORED_CONSTANTS
            if f == "native/session_bank.cpp"
        }
        declared = {
            k for k in native
            if k.startswith("kBankErr") or k.startswith("kHdr")
            or k.startswith("kFlag") or k.startswith("kReq")
            or k.startswith("kStage")
        } - {"kHdrStride"}  # stride is pinned by the header check
        assert declared <= mirrored, (
            f"unmirrored native constants: {sorted(declared - mirrored)}"
        )

    def test_static_header_matches_live_dtype(self):
        header = static_bank_header()
        dtype = np.dtype(list(_native.BANK_HDR_FIELDS))
        assert header["stride"] == dtype.itemsize
        for name, fmt, offset in header["fields"]:
            assert dtype.fields[name][1] == offset
            assert np.dtype(fmt) == dtype.fields[name][0]
        assert tuple(dtype.names) == tuple(
            n for n, _, _ in LAYOUT_HEADER_FIELDS
        )

    def test_static_header_matches_runtime_probe(self):
        lib = _native.bank_lib()
        if lib is None or not hasattr(lib, "ggrs_bank_hdr_stride"):
            pytest.skip("no native bank library on this platform")
        assert int(lib.ggrs_bank_hdr_stride()) == \
            static_bank_header()["stride"]

    def test_descriptor_tables_match_live_dtypes_and_probes(self):
        """The §21 contract tables equal both the live np.dtypes and the
        runtime stride probes."""
        for fields, contract, stride in (
            (_native.BANK_REQ_FIELDS, LAYOUT_REQ_FIELDS,
             LAYOUT_REQ_STRIDE),
            (_native.BANK_STAGE_FIELDS, LAYOUT_STAGE_FIELDS,
             LAYOUT_STAGE_STRIDE),
            (_native.BANK_WIRE_FIELDS, LAYOUT_WIRE_FIELDS,
             LAYOUT_WIRE_STRIDE),
            (_native.NET_SEND_FIELDS, LAYOUT_SEND_FIELDS,
             LAYOUT_SEND_STRIDE),
            (_native.NET_RECV_FIELDS, LAYOUT_RECV_FIELDS,
             LAYOUT_RECV_STRIDE),
            (_native.NET_ROUTE_FIELDS, LAYOUT_ROUTE_FIELDS,
             LAYOUT_ROUTE_STRIDE),
            (_native.NET_FD_FIELDS, LAYOUT_FD_FIELDS,
             LAYOUT_FD_STRIDE),
        ):
            dtype = np.dtype(list(fields))
            assert dtype.itemsize == stride
            for name, fmt, offset in contract:
                assert dtype.fields[name][1] == offset
                assert np.dtype(fmt) == dtype.fields[name][0]
        lib = _native.bank_lib()
        if lib is None or not hasattr(lib, "ggrs_bank_req_stride"):
            pytest.skip("no descriptor-plane library on this platform")
        assert int(lib.ggrs_bank_req_stride()) == LAYOUT_REQ_STRIDE
        assert int(lib.ggrs_bank_stage_stride()) == LAYOUT_STAGE_STRIDE
        assert int(lib.ggrs_bank_wire_stride()) == LAYOUT_WIRE_STRIDE

    def test_gen2_tables_match_runtime_probes(self):
        """The §23 drain/route/fd strides and stat-table widths equal the
        built library's probes (compiled on BOTH branches, so this pins
        the stub too)."""
        lib = _native.bank_lib()
        if lib is None or not hasattr(lib, "ggrs_net_recv_stride"):
            pytest.skip("no gen-2 library on this platform")
        assert int(lib.ggrs_net_recv_stride()) == LAYOUT_RECV_STRIDE
        assert int(lib.ggrs_net_route_stride()) == LAYOUT_ROUTE_STRIDE
        assert int(lib.ggrs_net_fd_stride()) == LAYOUT_FD_STRIDE
        assert int(lib.ggrs_net_send_stats_len()) == _native.NET_SEND_STATS
        assert int(lib.ggrs_net_recv_stats_len()) == \
            _native.NET_RECV_TABLE_STATS

    def test_cmd_flags_match_native_literals(self):
        native = parse_cpp_constants(REPO / "native/session_bank.cpp")
        assert _native.CMD_FLAG_INPUTS == native["kFlagInputs"]
        assert _native.CMD_FLAG_SKIP == native["kFlagSkip"]


class TestReviewRegressions:
    def test_enum_implicit_poisoned_after_unevaluable_entry(self):
        # B's true value is sizeof(int)+1, unknown statically: emitting
        # an implicit guess could mask (or fabricate) ABI drift
        c = parse_cpp_constants(
            "enum { kA = sizeof(int), kB, kC, kD = 9, kE };"
        )
        assert "kB" not in c and "kC" not in c
        assert c["kD"] == 9 and c["kE"] == 10

    def test_py_mirror_pair_drift_fires(self, tmp_path):
        from ggrs_tpu.analysis.layout import _check_py_mirrors

        (tmp_path / "a.py").write_text("P = 4\n")
        (tmp_path / "b.py").write_text("_P = 5\n")
        findings = _check_py_mirrors(
            tmp_path, [("a.py", "P", "b.py", "_P")]
        )
        assert [f.rule for f in findings] == ["layout/mirror-mismatch"]

    def test_pickle_protocol_pair_is_checked_on_tree(self):
        from ggrs_tpu.analysis.layout import PY_MIRRORED_CONSTANTS

        pairs = {(a, b) for a, _, b, _ in PY_MIRRORED_CONSTANTS}
        assert (
            "ggrs_tpu/fleet/rpc.py", "ggrs_tpu/parallel/host_bank.py"
        ) in pairs

    def test_unsigned_complement_uses_cast_width(self):
        c = parse_cpp_constants(
            "constexpr uint32_t kMask32 = ~uint32_t{0};\n"
            "constexpr uint64_t kMask64 = ~uint64_t{0};\n"
            "constexpr uint8_t kMask8 = ~uint8_t{0};\n"
        )
        assert c["kMask32"] == 0xFFFFFFFF
        assert c["kMask64"] == (1 << 64) - 1
        assert c["kMask8"] == 0xFF


# ----------------------------------------------------------------------
# §25 TCP handshake contract (PR 11 rule: wire structs land with their
# checker — deliberate-skew fixtures prove the checker catches drift)
# ----------------------------------------------------------------------

TP_GOOD = """\
import struct
HS_VERSION = 1
NONCE_BYTES = 16
MAC_BYTES = 32
CHALLENGE = struct.Struct("<2sBB16s")
AUTH_PREFIX = struct.Struct("<2sBBQQ16s")
AUTH = struct.Struct("<2sBBQQ16s32s")
VERDICT = struct.Struct("<2sBBQQ")
"""


class TestTcpHandshakeSkew:
    def _tree(self, tmp_path, text):
        (tmp_path / "ggrs_tpu/fleet").mkdir(parents=True)
        (tmp_path / "ggrs_tpu/fleet/transport.py").write_text(text)
        return tmp_path

    def _check(self, root):
        from ggrs_tpu.analysis.layout import _check_tcp_handshake
        return _check_tcp_handshake(root)

    def test_clean_fixture_passes(self, tmp_path):
        assert self._check(self._tree(tmp_path, TP_GOOD)) == []

    def test_auth_epoch_field_drift_fires(self, tmp_path):
        # shrinking the epoch from u64 to u32 must fire: a truncated
        # epoch is exactly the fence-defeating skew
        bad = TP_GOOD.replace('"<2sBBQQ16s"', '"<2sBBIQ16s"')
        findings = self._check(self._tree(tmp_path, bad))
        assert any(
            f.rule == "layout/tcp-handshake" and "auth prefix" in f.detail
            for f in findings
        )

    def test_resume_cursor_drift_fires(self, tmp_path):
        # dropping the resume cursor from the verdict fires
        bad = TP_GOOD.replace('"<2sBBQQ")', '"<2sBBQ")')
        findings = self._check(self._tree(tmp_path, bad))
        assert any(
            f.rule == "layout/tcp-handshake" and "verdict" in f.detail
            for f in findings
        )

    def test_mac_tail_drift_fires(self, tmp_path):
        # a 16-byte mac tail breaks auth = prefix + MAC_BYTES
        bad = TP_GOOD.replace('"<2sBBQQ16s32s"', '"<2sBBQQ16s16s"')
        findings = self._check(self._tree(tmp_path, bad))
        assert any("auth record" in f.detail or "mac" in f.detail
                   for f in findings)

    def test_mac_bytes_constant_drift_fires(self, tmp_path):
        bad = TP_GOOD.replace("MAC_BYTES = 32", "MAC_BYTES = 20")
        findings = self._check(self._tree(tmp_path, bad))
        assert any("MAC_BYTES" in f.detail for f in findings)

    def test_unversioned_handshake_fires(self, tmp_path):
        bad = TP_GOOD.replace("HS_VERSION = 1\n", "")
        findings = self._check(self._tree(tmp_path, bad))
        assert any("HS_VERSION" in f.detail for f in findings)

    def test_contract_matches_live_structs(self):
        from ggrs_tpu.analysis.layout import (
            TCP_AUTH_FMT,
            TCP_AUTH_PREFIX_FMT,
            TCP_CHALLENGE_FMT,
            TCP_VERDICT_FMT,
        )
        from ggrs_tpu.fleet import transport

        assert transport.CHALLENGE.format == TCP_CHALLENGE_FMT
        assert transport.AUTH_PREFIX.format == TCP_AUTH_PREFIX_FMT
        assert transport.AUTH.format == TCP_AUTH_FMT
        assert transport.VERDICT.format == TCP_VERDICT_FMT
        assert transport.AUTH.size == transport.AUTH_PREFIX.size + 32


# ----------------------------------------------------------------------
# §26 ingress wire contract (same PR 11 rule: wire structs land with
# their checker — deliberate-skew fixtures prove the checker catches
# drift in the forwarded-datagram header and route-update frame)
# ----------------------------------------------------------------------

ING_GOOD = """\
import struct
FWD_VERSION = 1
ROUTE_WIRE_VERSION = 2
ROUTE_OP_PUT = 1
ROUTE_OP_DEL = 2
FWD_HEADER = struct.Struct("<2sBBHH4s")
ROUTE_UPDATE = struct.Struct("<2sBBQQHH4s16s")
"""


class TestIngressWireSkew:
    def _tree(self, tmp_path, text):
        (tmp_path / "ggrs_tpu/fleet").mkdir(parents=True)
        (tmp_path / "ggrs_tpu/fleet/ingress.py").write_text(text)
        return tmp_path

    def _check(self, root):
        from ggrs_tpu.analysis.layout import _check_ingress_wire
        return _check_ingress_wire(root)

    def test_clean_fixture_passes(self, tmp_path):
        assert self._check(self._tree(tmp_path, ING_GOOD)) == []

    def test_fence_word_drift_fires(self, tmp_path):
        # shrinking the route epoch from u64 to u32 must fire: a
        # truncated epoch is exactly the fence-defeating skew that
        # would let a stale supervisor's route write wrap around
        bad = ING_GOOD.replace('"<2sBBQQHH4s16s"', '"<2sBBIQHH4s16s"')
        findings = self._check(self._tree(tmp_path, bad))
        assert any(
            f.rule == "layout/ingress-wire" and "route-update" in f.detail
            for f in findings
        )

    def test_fwd_header_drift_fires(self, tmp_path):
        # dropping the source-port word breaks peer-return routing
        bad = ING_GOOD.replace('"<2sBBHH4s"', '"<2sBBH4s"')
        findings = self._check(self._tree(tmp_path, bad))
        assert any(
            f.rule == "layout/ingress-wire"
            and "forwarded-datagram" in f.detail
            for f in findings
        )

    def test_unversioned_route_frame_fires(self, tmp_path):
        bad = ING_GOOD.replace("ROUTE_WIRE_VERSION = 2\n", "")
        findings = self._check(self._tree(tmp_path, bad))
        assert any("ROUTE_WIRE_VERSION" in f.detail for f in findings)

    def test_route_op_drift_fires(self, tmp_path):
        # the decode path refuses everything outside PUT=1/DEL=2; an
        # opcode renumber silently turns deletes into puts on old nodes
        bad = ING_GOOD.replace("ROUTE_OP_DEL = 2", "ROUTE_OP_DEL = 3")
        findings = self._check(self._tree(tmp_path, bad))
        assert any("route ops" in f.detail for f in findings)

    def test_contract_matches_live_structs(self):
        from ggrs_tpu.analysis.layout import (
            ING_FENCE_BYTES,
            ING_FWD_FMT,
            ING_ROUTE_FMT,
            TRACE_CTX_BYTES,
        )
        from ggrs_tpu.fleet import ingress

        assert ingress.FWD_HEADER.format == ING_FWD_FMT
        assert ingress.ROUTE_UPDATE.format == ING_ROUTE_FMT
        assert (ingress.ROUTE_UPDATE.size
                == ingress.FWD_HEADER.size + ING_FENCE_BYTES
                + TRACE_CTX_BYTES)


# ----------------------------------------------------------------------
# §28 trace-context contract: timeline.py owns the 16-byte context,
# transport.py mirrors it as a literal, the route frame tails it —
# deliberate-skew fixtures prove the checker catches each drifting alone
# ----------------------------------------------------------------------

TC_TL_GOOD = """\
import struct
TRACE_CTX_FMT = "<QII"
TRACE_CTX = struct.Struct("<QII")
TRACE_CTX_BYTES = 16
"""

TC_TP_GOOD = """\
import struct
TRACE_CTX_BYTES = 16
_TRACE = struct.Struct("<QII")
"""


class TestTraceContextSkew:
    def _tree(self, tmp_path, tl_text=TC_TL_GOOD, tp_text=TC_TP_GOOD):
        (tmp_path / "ggrs_tpu/obs").mkdir(parents=True)
        (tmp_path / "ggrs_tpu/fleet").mkdir(parents=True)
        (tmp_path / "ggrs_tpu/obs/timeline.py").write_text(tl_text)
        (tmp_path / "ggrs_tpu/fleet/transport.py").write_text(tp_text)
        return tmp_path

    def _check(self, root):
        from ggrs_tpu.analysis.layout import _check_trace_context
        return _check_trace_context(root)

    def test_clean_fixture_passes(self, tmp_path):
        assert self._check(self._tree(tmp_path)) == []

    def test_timeline_fmt_drift_fires(self, tmp_path):
        # shrinking the span word breaks every already-written 16-byte
        # tail on the wire — the owner drifting is the worst skew
        bad = TC_TL_GOOD.replace('"<QII"', '"<QIH"')
        findings = self._check(self._tree(tmp_path, tl_text=bad))
        assert any(
            f.rule == "layout/trace-context"
            and f.path == "ggrs_tpu/obs/timeline.py"
            for f in findings
        )

    def test_transport_mirror_drift_fires(self, tmp_path):
        # transport.py mirrors the struct as a literal (it cannot import
        # the obs plane into the runner hot path); a drifted mirror
        # corrupts every RPC-carried context
        bad = TP_GOOD.replace('"<QII"', '"<QQ"')
        findings = self._check(self._tree(tmp_path, tp_text=bad))
        assert any(
            f.rule == "layout/trace-context"
            and f.path == "ggrs_tpu/fleet/transport.py"
            for f in findings
        )

    def test_byte_count_drift_fires(self, tmp_path):
        bad = TC_TL_GOOD.replace("TRACE_CTX_BYTES = 16", "TRACE_CTX_BYTES = 12")
        findings = self._check(self._tree(tmp_path, tl_text=bad))
        assert any("TRACE_CTX_BYTES" in f.detail for f in findings)

    def test_contract_matches_live_structs(self):
        from ggrs_tpu.analysis import layout
        from ggrs_tpu.fleet import transport
        from ggrs_tpu.obs import timeline

        assert timeline.TRACE_CTX.format == layout.TRACE_CTX_FMT
        assert timeline.TRACE_CTX_BYTES == layout.TRACE_CTX_BYTES == 16
        assert timeline.TRACE_CTX.size == timeline.TRACE_CTX_BYTES
        assert transport.TRACE_CTX_BYTES == layout.TRACE_CTX_BYTES
        assert layout.ING_ROUTE_FMT.endswith(
            f"{layout.TRACE_CTX_BYTES}s")


VARREC_GOOD = """\
import struct
VARREC_HEADER_FMT = "<H"
VARREC_HEADER_BYTES = 2
VARREC_MAX_CAPACITY = 0xFFFF
def envelope_pack(payload, capacity):
    return struct.pack("<H", len(payload)) + payload
"""

RTSCMD_GOOD = """\
from ..core.varrec import VARREC_HEADER_BYTES
CMD_BYTES = 4
"""


class TestVarrecSkew:
    """§27 envelope contract: the [u16 len][payload][pad] framing is
    what makes variable-size inputs native-eligible, so a drifted
    header silently desyncs every varrec match — the fixtures prove the
    checker fires before that can land."""

    def _tree(self, tmp_path, varrec_text, rtscmd_text=RTSCMD_GOOD):
        (tmp_path / "ggrs_tpu/core").mkdir(parents=True)
        (tmp_path / "ggrs_tpu/games").mkdir(parents=True)
        (tmp_path / "ggrs_tpu/core/varrec.py").write_text(varrec_text)
        (tmp_path / "ggrs_tpu/games/rtscmd.py").write_text(rtscmd_text)
        return tmp_path

    def _check(self, root):
        from ggrs_tpu.analysis.layout import _check_varrec
        return _check_varrec(root)

    def test_clean_fixture_passes(self, tmp_path):
        assert self._check(self._tree(tmp_path, VARREC_GOOD)) == []

    def test_header_fmt_drift_fires(self, tmp_path):
        # widening the length prefix to u32 shifts every payload byte:
        # old and new nodes would decode different records from the
        # same envelope
        bad = VARREC_GOOD.replace('"<H"', '"<I"')
        findings = self._check(self._tree(tmp_path, bad))
        assert any(
            f.rule == "layout/varrec-header"
            and "length prefix" in f.detail
            for f in findings
        )

    def test_header_width_drift_fires(self, tmp_path):
        # the byte-literal width is what the device-side decode and the
        # native jump offsets consume; it must track the fmt
        bad = VARREC_GOOD.replace("VARREC_HEADER_BYTES = 2",
                                  "VARREC_HEADER_BYTES = 4")
        findings = self._check(self._tree(tmp_path, bad))
        assert any(
            f.rule == "layout/varrec-header"
            and "VARREC_HEADER_BYTES" in f.detail
            for f in findings
        )

    def test_capacity_bound_drift_fires(self, tmp_path):
        # a capacity past the u16 length prefix's reach could frame
        # payloads whose length does not round-trip
        bad = VARREC_GOOD.replace("0xFFFF", "0x1FFFF")
        findings = self._check(self._tree(tmp_path, bad))
        assert any(f.rule == "layout/varrec-capacity" for f in findings)

    def test_consumer_literal_offset_fires(self, tmp_path):
        # the in-kernel decode must read the header width through the
        # shared constant — a hand-inlined 2 drifts silently when the
        # envelope changes
        findings = self._check(self._tree(
            tmp_path, VARREC_GOOD,
            rtscmd_text="CMD_BYTES = 4\nHEADER = 2\n",
        ))
        assert any(f.rule == "layout/varrec-consumer" for f in findings)

    def test_contract_matches_live_module(self):
        from ggrs_tpu.analysis.layout import (
            VARREC_HEADER_BYTES,
            VARREC_HEADER_FMT,
            VARREC_MAX_CAPACITY,
        )
        from ggrs_tpu.core import varrec

        assert varrec.VARREC_HEADER_FMT == VARREC_HEADER_FMT
        assert varrec.VARREC_HEADER_BYTES == VARREC_HEADER_BYTES
        assert varrec.VARREC_MAX_CAPACITY == VARREC_MAX_CAPACITY
        assert varrec.envelope_size(60) == 60 + VARREC_HEADER_BYTES
