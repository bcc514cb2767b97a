"""tpu-lint v3 tentpole: the native-boundary ABI checker.

Three layers, mirroring the rule's own structure:

- the clang-free C tokenizer (analysis/cparse.py) on inline sources;
- the `native-abi-contract` project rule on the fixture trio
  (tests/lint_fixtures/project/nativeabi*), including the acceptance
  drift pair — one changed argtype width, one removed ``extern "C"``
  symbol — plus missing restype, undeclared export, and a call-site
  dtype drift;
- the real tree: the static model of native/*.cpp vs the live ctypes
  table in backends/native_slot_table.py must agree (and the rule must
  be clean at HEAD), so the parser is exercised against the actual
  serving surface, not just fixtures.
"""

import ctypes
from pathlib import Path

import pytest

from ratelimit_tpu.analysis.cparse import (
    extern_c_regions,
    parse_source,
    parse_sources,
    strip_comments,
)
from ratelimit_tpu.analysis.engine import analyze_paths
from ratelimit_tpu.analysis.native_abi import (
    find_native_sources,
    make_native_abi_rules,
)
from ratelimit_tpu.backends import native_slot_table as nst

FIXTURES = Path(__file__).parent / "lint_fixtures" / "project"
REPO_ROOT = Path(__file__).parent.parent
BINDING = REPO_ROOT / "ratelimit_tpu" / "backends" / "native_slot_table.py"


def abi_findings(subdir):
    findings, _ = analyze_paths(
        [str(FIXTURES / subdir)],
        rules=[],
        project_rules=make_native_abi_rules(),
    )
    return findings


# -- the C tokenizer ---------------------------------------------------------


def test_cparse_block_form_signatures():
    model = parse_source(
        "mem.cpp",
        text="""
#include <cstdint>
extern "C" {
int64_t f(const uint8_t* blob, int64_t n);
void g(void* h) { /* body with } brace in comment */ }
float h(float x, double y, uint32_t* out);
}
""",
    )
    assert set(model.functions) == {"f", "g", "h"}
    f = model.functions["f"]
    assert f.ret.describe() == "int64_t"
    assert [p.ctype.describe() for p in f.params] == ["uint8_t*", "int64_t"]
    assert [p.name for p in f.params] == ["blob", "n"]
    g = model.functions["g"]
    assert g.ret.describe() == "void"
    assert [p.ctype.describe() for p in g.params] == ["void*"]
    h = model.functions["h"]
    assert [p.ctype.describe() for p in h.params] == [
        "float",
        "double",
        "uint32_t*",
    ]


def test_cparse_one_shot_form_and_void_params():
    model = parse_source(
        "one.cpp",
        text="""
extern "C" int64_t lone(void);
extern "C" void* maker(int64_t cap) { return nullptr; }
int64_t not_exported(int64_t x) { return x; }
""",
    )
    assert set(model.functions) == {"lone", "maker"}
    assert model.functions["lone"].params == []  # f(void) normalizes
    assert model.functions["maker"].ret.describe() == "void*"


def test_cparse_ignores_comments_strings_and_nested_bodies():
    model = parse_source(
        "noise.cpp",
        text="""
// extern "C" void commented_out(void* h);
static const char* s = "extern \\"C\\" void fake(int64_t n);";
extern "C" {
/* int64_t also_commented(void* h); */
void real(void* h) {
  if (h) { helper(1, 2); }  // calls inside bodies are not signatures
}
}
""",
    )
    assert set(model.functions) == {"real"}


def test_cparse_line_numbers_and_constants():
    text = 'constexpr uint64_t kCeil = 0xFFull;\nextern "C" {\nvoid a(void* h);\n\nint64_t b(void* h);\n}\n'
    model = parse_source("lines.cpp", text=text)
    assert model.constants == {"kCeil": 0xFF}
    assert model.functions["a"].line == 3
    assert model.functions["b"].line == 5


def test_cparse_unknown_type_punts_not_guesses():
    model = parse_source(
        "odd.cpp",
        text='extern "C" void takes(struct Foo* f, int64_t n);',
    )
    p0, p1 = model.functions["takes"].params
    assert p0.ctype.kind == "unknown" and p0.ctype.is_pointer
    assert p1.ctype.describe() == "int64_t"


def test_strip_comments_keeps_linkage_marker_and_newlines():
    src = '/* x */ extern "C" { // tail\nvoid f(void* h);\n}'
    clean = strip_comments(src)
    assert '"C"' in clean
    assert clean.count("\n") == src.count("\n")
    assert len(extern_c_regions(clean)) == 1


# -- the rule on fixtures ----------------------------------------------------


def test_injected_drift_pair_is_caught():
    """The acceptance drifts: one changed argtype width and one
    removed extern \"C\" symbol, each a distinct finding."""
    msgs = [f.message for f in abi_findings("nativeabi")]
    assert any(
        "rl_sum: argtypes[1] is c_int32" in m and "int64_t" in m
        for m in msgs
    ), msgs
    assert any(
        "declares rl_gone but no extern \"C\" function" in m for m in msgs
    ), msgs


def test_fixture_full_finding_set():
    findings = abi_findings("nativeabi")
    assert len(findings) == 5, [f.text() for f in findings]
    assert all(f.rule_id == "native-abi-contract" for f in findings)
    # every finding anchors in the binding .py (suppressible), naming
    # the C site in the message
    assert all(f.path.endswith("binding.py") for f in findings)
    msgs = " | ".join(f.message for f in findings)
    assert "rl_extra" in msgs and "no ctypes argtypes" in msgs
    assert "rl_count" in msgs and "truncates 64-bit returns" in msgs
    assert "np.int32 buffer" in msgs and "out of bounds" in msgs
    assert "native_src.cpp:" in msgs  # C file:line navigation


def test_clean_binding_true_negative():
    assert abi_findings("nativeabi_ok") == []


def test_suppression_honored_with_reason():
    assert abi_findings("nativeabi_suppressed") == []


# -- the real tree -----------------------------------------------------------

EXPORTS = {
    "sk_create",
    "sk_destroy",
    "sk_len",
    "sk_evictions",
    "sk_arena_bytes",
    "sk_compactions",
    "sk_gc",
    "sk_begin_batch",
    "sk_end_batch",
    "sk_assign_batch",
    "sk_assign_dedup_batch",
    "sk_export_size",
    "sk_export",
    "sk_import",
    "sk_decide_reconstruct",
}


def test_real_sources_discovered_and_fully_parsed():
    srcs = find_native_sources(str(BINDING))
    assert srcs, "native/*.cpp not found from the binding module"
    model = parse_sources(srcs)
    assert set(model.functions) == EXPORTS
    assert model.functions["sk_create"].ret.describe() == "void*"
    assert len(model.functions["sk_assign_dedup_batch"].params) == 15
    assert len(model.functions["sk_decide_reconstruct"].params) == 22
    # no parameter on the real surface defeats the lexer
    for fn in model.functions.values():
        for p in fn.params:
            assert p.ctype.kind != "unknown", (fn.name, p)
    assert model.constants.get("kU32Max") == 0xFFFFFFFF


def test_real_binding_clean_at_head():
    """The shipped ctypes table agrees with native/*.cpp — the rule's
    zero-findings guarantee on the actual serving boundary."""
    findings, _ = analyze_paths(
        [str(REPO_ROOT / "ratelimit_tpu" / "backends")],
        rules=[],
        project_rules=make_native_abi_rules(),
    )
    assert findings == [], [f.text() for f in findings]


def test_expected_symbols_matches_static_model():
    """The loader's preflight symbol set is derived from _signatures
    itself, so it can't drift from the table; it must also equal the
    statically parsed export set."""
    assert nst.expected_symbols() == EXPORTS


def test_live_library_agrees_with_static_model():
    if not nst.available():
        pytest.skip("native library unavailable in this environment")
    lib = ctypes.CDLL(nst.loaded_path())
    model = parse_sources(find_native_sources(str(BINDING)))
    for name, fn in model.functions.items():
        assert hasattr(lib, name), name
    assert nst._missing_symbols(lib) == []


# -- loader preflight (ISSUE 16 satellite) -----------------------------------


class _FakeLib:
    """hasattr-only stand-in for a dlopen'd library exporting a
    subset of the surface."""

    def __init__(self, *names):
        for n in names:
            setattr(self, n, object())


def test_missing_symbols_preflight_lists_gaps():
    fake = _FakeLib("sk_create", "sk_destroy", "sk_len")
    missing = nst._missing_symbols(fake)
    assert "sk_assign_dedup_batch" in missing
    assert "sk_decide_reconstruct" in missing
    assert "sk_create" not in missing


def test_verify_symbols_warns_with_rebuild_hint(caplog):
    import logging

    with caplog.at_level(logging.WARNING, logger=nst.logger.name):
        ok = nst._verify_symbols(_FakeLib("sk_create"), "/tmp/stale.so")
    assert ok is False
    assert "run `make native` to rebuild" in caplog.text
    assert "sk_assign_batch" in caplog.text  # names what is missing


def test_verify_symbols_clean_on_full_surface():
    full = _FakeLib(*nst.expected_symbols())
    assert nst._verify_symbols(full, "x.so") is True


def test_native_so_override_pins_and_degrades(tmp_path):
    """TPU_NATIVE_SO loads the named library verbatim; a bad path
    degrades to the Python table (available() False) instead of
    raising."""
    import subprocess
    import sys

    if not nst.available():
        pytest.skip("native library unavailable in this environment")
    prog = (
        "from ratelimit_tpu.backends import native_slot_table as n;"
        "import sys;"
        "sys.exit(0 if n.available() == (len(sys.argv) > 1) and "
        "(not n.available() or n.loaded_path() == "
        "__import__('os').environ['TPU_NATIVE_SO']) else 1)"
    )
    import os

    env = dict(os.environ, TPU_NATIVE_SO=nst._SO)
    rc = subprocess.run(
        [sys.executable, "-c", prog, "expect-available"], env=env
    ).returncode
    assert rc == 0, "override with a valid .so must load exactly that path"
    env = dict(os.environ, TPU_NATIVE_SO=str(tmp_path / "nope.so"))
    rc = subprocess.run([sys.executable, "-c", prog], env=env).returncode
    assert rc == 0, "override with a missing .so must degrade, not raise"
